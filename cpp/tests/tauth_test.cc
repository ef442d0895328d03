// Authenticator hooks: tpu_std first-message auth (+ the auth fight on
// shared connections) and gRPC authorization-header verification.
// Reference parity: src/brpc/authenticator.h, protocol.h verify hook,
// socket.h:515 FightAuthentication.
#include <atomic>
#include <string>
#include <vector>

#include "echo.pb.h"
#include "tbase/endpoint.h"
#include "tbase/errno.h"
#include "tbase/time.h"
#include "tfiber/fiber.h"
#include "trpc/auth.h"
#include "trpc/channel.h"
#include "trpc/controller.h"
#include "trpc/server.h"
#include "ttest/ttest.h"

using namespace tpurpc;

namespace {

class CountingAuth : public Authenticator {
public:
    explicit CountingAuth(std::string secret, bool present_wrong = false)
        : secret_(std::move(secret)), present_wrong_(present_wrong) {}

    int GenerateCredential(std::string* auth_str) const override {
        generated_.fetch_add(1);
        *auth_str = present_wrong_ ? "wrong-" + secret_ : secret_;
        return 0;
    }

    int VerifyCredential(const std::string& auth_str, const EndPoint&,
                         AuthContext* ctx) const override {
        verified_.fetch_add(1);
        if (auth_str != secret_) return -1;
        ctx->set_user("tester");
        return 0;
    }

    int generated() const { return generated_.load(); }
    int verified() const { return verified_.load(); }

private:
    std::string secret_;
    bool present_wrong_;
    mutable std::atomic<int> generated_{0};
    mutable std::atomic<int> verified_{0};
};

class AuthEchoImpl : public test::EchoService {
public:
    // The budget (us) the call named "budget" still had as its handler
    // was entered, by the deadline its caller sent.
    std::atomic<int64_t> budget_seen_us{-1};

    void Echo(google::protobuf::RpcController* c,
              const test::EchoRequest* request, test::EchoResponse* response,
              google::protobuf::Closure* done) override {
        if (request->message() == "budget") {
            budget_seen_us.store(
                static_cast<Controller*>(c)->server_deadline_us() -
                monotonic_time_us());
        }
        if (request->sleep_us() > 0) fiber_usleep(request->sleep_us());
        response->set_message(request->message());
        done->Run();
    }
};

struct AuthServer {
    AuthEchoImpl service;
    Server server;
    EndPoint ep;

    bool start(const Authenticator* auth) {
        if (server.AddService(&service) != 0) return false;
        ServerOptions opts;
        opts.auth = auth;
        EndPoint listen;
        str2endpoint("127.0.0.1:0", &listen);
        if (server.Start(listen, &opts) != 0) return false;
        str2endpoint("127.0.0.1", server.listened_port(), &ep);
        return true;
    }
};

int DoEcho(Channel* ch, const std::string& msg) {
    test::EchoService_Stub stub(ch);
    Controller cntl;
    test::EchoRequest req;
    req.set_message(msg);
    test::EchoResponse res;
    stub.Echo(&cntl, &req, &res, nullptr);
    if (cntl.Failed()) return cntl.ErrorCode();
    return res.message() == msg ? 0 : -1;
}

}  // namespace

TEST(Auth, GoodCredentialAccepted) {
    CountingAuth server_auth("s3cret");
    CountingAuth client_auth("s3cret");
    AuthServer ts;
    ASSERT_TRUE(ts.start(&server_auth));
    Channel ch;
    ChannelOptions opts;
    opts.auth = &client_auth;
    opts.timeout_ms = 5000;
    ASSERT_EQ(0, ch.Init(ts.ep, &opts));
    EXPECT_EQ(0, DoEcho(&ch, "hello"));
    EXPECT_EQ(0, DoEcho(&ch, "again"));
    // Credential generated + verified once: the connection is trusted
    // after the first message (no per-request re-verification).
    EXPECT_EQ(client_auth.generated(), 1);
    EXPECT_EQ(server_auth.verified(), 1);
}

TEST(Auth, BadCredentialRejectedAndConnectionFailed) {
    CountingAuth server_auth("s3cret");
    CountingAuth client_auth("s3cret", /*present_wrong=*/true);
    AuthServer ts;
    ASSERT_TRUE(ts.start(&server_auth));
    Channel ch;
    ChannelOptions opts;
    opts.auth = &client_auth;
    opts.max_retry = 0;
    opts.timeout_ms = 5000;
    ASSERT_EQ(0, ch.Init(ts.ep, &opts));
    test::EchoService_Stub stub(&ch);
    Controller cntl;
    test::EchoRequest req;
    req.set_message("x");
    test::EchoResponse res;
    stub.Echo(&cntl, &req, &res, nullptr);
    EXPECT_TRUE(cntl.Failed());
    EXPECT_EQ(cntl.ErrorCode(), TERR_AUTH);
}

TEST(Auth, MissingCredentialRejected) {
    CountingAuth server_auth("s3cret");
    AuthServer ts;
    ASSERT_TRUE(ts.start(&server_auth));
    Channel ch;  // NO authenticator on the client
    ChannelOptions opts;
    opts.max_retry = 0;
    opts.timeout_ms = 5000;
    ASSERT_EQ(0, ch.Init(ts.ep, &opts));
    test::EchoService_Stub stub(&ch);
    Controller cntl;
    test::EchoRequest req;
    req.set_message("x");
    test::EchoResponse res;
    stub.Echo(&cntl, &req, &res, nullptr);
    EXPECT_TRUE(cntl.Failed());
}

TEST(Auth, ConcurrentFirstWritesAuthenticateExactlyOnce) {
    // 16 fibers race the FIRST calls on one shared connection: exactly
    // one attaches the credential (the others wait out the fight), and
    // every call succeeds.
    CountingAuth server_auth("s3cret");
    CountingAuth client_auth("s3cret");
    AuthServer ts;
    ASSERT_TRUE(ts.start(&server_auth));
    Channel ch;
    ChannelOptions opts;
    opts.auth = &client_auth;
    opts.timeout_ms = 10000;
    ASSERT_EQ(0, ch.Init(ts.ep, &opts));
    struct Ctx {
        Channel* ch;
        std::atomic<int> ok{0};
    } ctx{&ch, {}};
    std::vector<fiber_t> tids(16);
    for (auto& tid : tids) {
        fiber_start_background(
            &tid, nullptr,
            [](void* arg) -> void* {
                Ctx* c = (Ctx*)arg;
                if (DoEcho(c->ch, "fight") == 0) c->ok.fetch_add(1);
                return nullptr;
            },
            &ctx);
    }
    for (auto tid : tids) fiber_join(tid, nullptr);
    EXPECT_EQ(ctx.ok.load(), 16);
    EXPECT_EQ(client_auth.generated(), 1);
    EXPECT_EQ(server_auth.verified(), 1);
    EXPECT_EQ(ts.server.acceptor()->accepted_count(), 1);
}

TEST(Auth, BudgetSentAfterTheAuthWaitIsWhatIsLeftOfIt) {
    // The first caller wins the auth fight and its handler sleeps 400 ms,
    // so the connection stays unauthenticated that long. A second caller
    // with a 1000 ms timeout waits the fight out inside IssueRPC
    // (WaitAuthenticated) and only then builds its request: the
    // timeout_ms it sends is what is LEFT of its budget at that moment,
    // not what it had when CallMethod was entered -- a server must not be
    // told it may work for time the caller has already spent waiting.
    CountingAuth server_auth("s3cret");
    CountingAuth client_auth("s3cret");
    AuthServer ts;
    ASSERT_TRUE(ts.start(&server_auth));
    Channel ch;
    ChannelOptions opts;
    opts.auth = &client_auth;
    opts.timeout_ms = 1000;
    opts.max_retry = 0;
    ASSERT_EQ(0, ch.Init(ts.ep, &opts));
    fiber_t winner;
    fiber_start_background(
        &winner, nullptr,
        [](void* arg) -> void* {
            test::EchoService_Stub stub((Channel*)arg);
            Controller cntl;
            test::EchoRequest req;
            req.set_message("winner");
            req.set_sleep_us(400 * 1000);
            test::EchoResponse res;
            stub.Echo(&cntl, &req, &res, nullptr);
            return nullptr;
        },
        &ch);
    fiber_usleep(50 * 1000);  // the winner's request is out by now
    const int64_t t0 = monotonic_time_us();
    ASSERT_EQ(0, DoEcho(&ch, "budget"));
    const int64_t waited_us = monotonic_time_us() - t0;
    fiber_join(winner, nullptr);
    EXPECT_EQ(client_auth.generated(), 1);  // it waited, it did not fight
    EXPECT_GE(waited_us, 250 * 1000);
    const int64_t seen_us = ts.service.budget_seen_us.load();
    // ~1000 - 350 ms were left; ms rounding and the trip to the handler
    // take a few more. Sending the budget as of CallMethod reads ~1000.
    EXPECT_GT(seen_us, 300 * 1000);
    EXPECT_LT(seen_us, 800 * 1000);
}

TEST(AuthGrpc, HeaderVerifiedPerCall) {
    CountingAuth server_auth("Bearer tok-123");
    CountingAuth good("Bearer tok-123");
    CountingAuth bad("Bearer tok-123", /*present_wrong=*/true);
    AuthServer ts;
    ASSERT_TRUE(ts.start(&server_auth));
    {
        Channel ch;
        ChannelOptions opts;
        opts.protocol = "grpc";
        opts.auth = &good;
        opts.timeout_ms = 10000;
        ASSERT_EQ(0, ch.Init(ts.ep, &opts));
        EXPECT_EQ(0, DoEcho(&ch, "authed"));
    }
    {
        Channel ch;
        ChannelOptions opts;
        opts.protocol = "grpc";
        opts.auth = &bad;
        opts.max_retry = 0;
        opts.timeout_ms = 10000;
        ASSERT_EQ(0, ch.Init(ts.ep, &opts));
        test::EchoService_Stub stub(&ch);
        Controller cntl;
        test::EchoRequest req;
        req.set_message("x");
        test::EchoResponse res;
        stub.Echo(&cntl, &req, &res, nullptr);
        EXPECT_TRUE(cntl.Failed());  // grpc-status 16 UNAUTHENTICATED
    }
}

#include "trpc/redis.h"

TEST(AuthRedis, NoauthUntilAuthCommand) {
    // ServerOptions::auth covers RESP too: commands before a valid AUTH
    // get -NOAUTH; AUTH with the right credential unlocks the connection.
    CountingAuth server_auth("hunter2");
    AuthServer ts;
    ASSERT_TRUE(ts.start(&server_auth));
    RedisService kv;
    kv.AddBasicKvCommands();
    ts.server.set_redis_service(&kv);  // set post-start is fine for tests

    Channel ch;
    ChannelOptions opts;
    opts.protocol = "redis";
    opts.timeout_ms = 5000;
    ASSERT_EQ(0, ch.Init(ts.ep, &opts));

    RedisRequest req;
    req.AddCommand({"PING"});                 // -> NOAUTH
    req.AddCommand({"AUTH", "wrong"});        // -> ERR
    req.AddCommand({"AUTH", "hunter2"});      // -> OK
    req.AddCommand({"PING"});                 // -> PONG
    RedisResponse res;
    Controller cntl;
    RedisCall(&ch, &cntl, req, &res);
    ASSERT_FALSE(cntl.Failed());
    ASSERT_EQ(res.reply_count(), 4u);
    EXPECT_TRUE(res.reply(0).is_error());
    EXPECT_EQ(res.reply(0).str.compare(0, 6, "NOAUTH"), 0);
    EXPECT_TRUE(res.reply(1).is_error());
    EXPECT_EQ(res.reply(2).str, "OK");
    EXPECT_EQ(res.reply(3).str, "PONG");
}

#include <netinet/in.h>
#include <sys/socket.h>
#include <unistd.h>

TEST(AuthHttp, JsonTranscodingRequiresAuthorization) {
    // The json door honors ServerOptions::auth too: bare POST is 401,
    // with the credential in `authorization` it runs.
    CountingAuth server_auth("open-sesame");
    AuthServer ts;
    ASSERT_TRUE(ts.start(&server_auth));
    auto fetch = [&](const std::string& req_str) {
        const int fd = ::socket(AF_INET, SOCK_STREAM, 0);
        sockaddr_in addr;
        endpoint2sockaddr(ts.ep, &addr);
        if (::connect(fd, (sockaddr*)&addr, sizeof(addr)) != 0) {
            ::close(fd);
            return std::string("connect-failed");
        }
        (void)!::send(fd, req_str.data(), req_str.size(), 0);
        std::string out;
        char buf[4096];
        ssize_t r;
        while ((r = ::recv(fd, buf, sizeof(buf), 0)) > 0) {
            out.append(buf, (size_t)r);
            if (out.find("\r\n\r\n") != std::string::npos &&
                out.find("}") != std::string::npos) {
                break;
            }
        }
        ::close(fd);
        return out;
    };
    const std::string body = "{\"message\": \"sesame\"}";
    char req[512];
    snprintf(req, sizeof(req),
             "POST /EchoService/Echo HTTP/1.1\r\nHost: x\r\n"
             "Content-Type: application/json\r\nContent-Length: %zu\r\n"
             "\r\n%s",
             body.size(), body.c_str());
    const std::string denied = fetch(req);
    EXPECT_NE(denied.find("401"), std::string::npos);
    snprintf(req, sizeof(req),
             "POST /EchoService/Echo HTTP/1.1\r\nHost: x\r\n"
             "Authorization: open-sesame\r\n"
             "Content-Type: application/json\r\nContent-Length: %zu\r\n"
             "\r\n%s",
             body.size(), body.c_str());
    const std::string ok = fetch(req);
    EXPECT_NE(ok.find("200"), std::string::npos);
    EXPECT_NE(ok.find("sesame"), std::string::npos);
}
