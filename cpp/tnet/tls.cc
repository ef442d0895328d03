#include "tnet/tls.h"

#include <dlfcn.h>
#include <netinet/in.h>
#include <poll.h>
#include <sys/socket.h>
#include <unistd.h>

#include <algorithm>
#include <cerrno>
#include <cstring>
#include <mutex>

#include "tbase/endpoint.h"
#include "tbase/logging.h"
#include "tbase/time.h"
#include "tfiber/fiber.h"
#include "tnet/fault_injection.h"

namespace tpurpc {

namespace {

// ---- OpenSSL 3 ABI surface (hand-declared; resolved via dlsym) ----

typedef struct ssl_ctx_st SSL_CTX;
typedef struct ssl_st SSL;
typedef struct ssl_method_st SSL_METHOD;

constexpr int kSslFiletypePem = 1;       // SSL_FILETYPE_PEM
constexpr int kSslErrorWantRead = 2;     // SSL_ERROR_WANT_READ
constexpr int kSslErrorWantWrite = 3;    // SSL_ERROR_WANT_WRITE
constexpr int kSslErrorZeroReturn = 6;   // SSL_ERROR_ZERO_RETURN
constexpr int kSslCtrlMode = 33;         // SSL_CTRL_MODE
constexpr long kModePartialWrite = 0x1;  // SSL_MODE_ENABLE_PARTIAL_WRITE
constexpr long kModeMovingBuffer = 0x2;  // SSL_MODE_ACCEPT_MOVING_WRITE_BUFFER
constexpr int kCtrlSetTlsextHostname = 55;  // SSL_CTRL_SET_TLSEXT_HOSTNAME
constexpr int kTlsextNametypeHost = 0;      // TLSEXT_NAMETYPE_host_name

struct SslApi {
    void* handle = nullptr;
    int (*init_ssl)(uint64_t, const void*);
    const SSL_METHOD* (*tls_method)();
    SSL_CTX* (*ctx_new)(const SSL_METHOD*);
    void (*ctx_free)(SSL_CTX*);
    int (*use_cert_chain)(SSL_CTX*, const char*);
    int (*use_privkey)(SSL_CTX*, const char*, int);
    long (*ctx_ctrl)(SSL_CTX*, int, long, void*);
    int (*set_alpn_protos)(SSL*, const unsigned char*, unsigned);
    void (*ctx_set_alpn_select_cb)(
        SSL_CTX*,
        int (*)(SSL*, const unsigned char**, unsigned char*,
                const unsigned char*, unsigned, void*),
        void*);
    SSL* (*ssl_new)(SSL_CTX*);
    void (*ssl_free)(SSL*);
    int (*set_fd)(SSL*, int);
    void (*set_connect_state)(SSL*);
    void (*set_accept_state)(SSL*);
    int (*do_handshake)(SSL*);
    int (*ssl_read)(SSL*, void*, int);
    int (*ssl_write)(SSL*, const void*, int);
    int (*get_error)(const SSL*, int);
    int (*ssl_shutdown)(SSL*);
    long (*ssl_ctrl)(SSL*, int, long, void*);
    void (*get0_alpn_selected)(const SSL*, const unsigned char**,
                               unsigned*);
    void (*err_clear)();
};

SslApi* ssl_api() {
    static SslApi* api = []() -> SslApi* {
        void* h = dlopen("libssl.so.3", RTLD_NOW | RTLD_GLOBAL);
        if (h == nullptr) h = dlopen("libssl.so", RTLD_NOW | RTLD_GLOBAL);
        if (h == nullptr) return nullptr;
        auto* a = new SslApi;
        a->handle = h;
        bool ok = true;
        auto sym = [&](const char* name) -> void* {
            void* p = dlsym(h, name);
            if (p == nullptr) ok = false;
            return p;
        };
        a->init_ssl = (decltype(a->init_ssl))sym("OPENSSL_init_ssl");
        a->tls_method = (decltype(a->tls_method))sym("TLS_method");
        a->ctx_new = (decltype(a->ctx_new))sym("SSL_CTX_new");
        a->ctx_free = (decltype(a->ctx_free))sym("SSL_CTX_free");
        a->use_cert_chain = (decltype(a->use_cert_chain))sym(
            "SSL_CTX_use_certificate_chain_file");
        a->use_privkey =
            (decltype(a->use_privkey))sym("SSL_CTX_use_PrivateKey_file");
        a->ctx_ctrl = (decltype(a->ctx_ctrl))sym("SSL_CTX_ctrl");
        a->set_alpn_protos =
            (decltype(a->set_alpn_protos))sym("SSL_set_alpn_protos");
        a->ctx_set_alpn_select_cb = (decltype(a->ctx_set_alpn_select_cb))sym(
            "SSL_CTX_set_alpn_select_cb");
        a->ssl_new = (decltype(a->ssl_new))sym("SSL_new");
        a->ssl_free = (decltype(a->ssl_free))sym("SSL_free");
        a->set_fd = (decltype(a->set_fd))sym("SSL_set_fd");
        a->set_connect_state =
            (decltype(a->set_connect_state))sym("SSL_set_connect_state");
        a->set_accept_state =
            (decltype(a->set_accept_state))sym("SSL_set_accept_state");
        a->do_handshake = (decltype(a->do_handshake))sym("SSL_do_handshake");
        a->ssl_read = (decltype(a->ssl_read))sym("SSL_read");
        a->ssl_write = (decltype(a->ssl_write))sym("SSL_write");
        a->get_error = (decltype(a->get_error))sym("SSL_get_error");
        a->ssl_shutdown = (decltype(a->ssl_shutdown))sym("SSL_shutdown");
        a->ssl_ctrl = (decltype(a->ssl_ctrl))sym("SSL_ctrl");
        a->get0_alpn_selected = (decltype(a->get0_alpn_selected))sym(
            "SSL_get0_alpn_selected");
        a->err_clear = (decltype(a->err_clear))sym("ERR_clear_error");
        if (!ok) {
            dlclose(h);
            delete a;
            return nullptr;
        }
        a->init_ssl(0, nullptr);
        return a;
    }();
    return api;
}

// ALPN select callback: prefer h2, accept http/1.1.
int AlpnSelect(SSL*, const unsigned char** out, unsigned char* outlen,
               const unsigned char* in, unsigned inlen, void*) {
    const unsigned char* http11 = nullptr;
    unsigned char http11_len = 0;
    for (unsigned i = 0; i + 1 <= inlen;) {
        const unsigned char len = in[i];
        if (i + 1 + len > inlen) break;
        if (len == 2 && memcmp(in + i + 1, "h2", 2) == 0) {
            *out = in + i + 1;
            *outlen = len;
            return 0;  // SSL_TLSEXT_ERR_OK
        }
        if (len == 8 && memcmp(in + i + 1, "http/1.1", 8) == 0) {
            http11 = in + i + 1;
            http11_len = len;
        }
        i += 1 + len;
    }
    if (http11 != nullptr) {
        *out = http11;
        *outlen = http11_len;
        return 0;
    }
    return 3;  // SSL_TLSEXT_ERR_NOACK: proceed without ALPN
}

SSL_CTX* g_server_ctx = nullptr;
SSL_CTX* client_ctx() {
    static SSL_CTX* ctx = [] {
        SslApi* a = ssl_api();
        if (a == nullptr) return (SSL_CTX*)nullptr;
        SSL_CTX* c = a->ctx_new(a->tls_method());
        if (c != nullptr) {
            a->ctx_ctrl(c, kSslCtrlMode,
                        kModePartialWrite | kModeMovingBuffer, nullptr);
        }
        return c;
    }();
    return ctx;
}

// ---- the transport ----

class TlsTransport : public TransportEndpoint {
public:
    TlsTransport(SSL* ssl, int fd, SslApi* api)
        : ssl_(ssl), fd_(fd), api_(api) {
        // Remote identity for per-peer fault-injection scoping; best
        // effort (an unconnected fd leaves it empty = matches only
        // unscoped plans).
        sockaddr_in peer;
        socklen_t plen = sizeof(peer);
        if (getpeername(fd, (sockaddr*)&peer, &plen) == 0) {
            remote_ = sockaddr2endpoint(peer);
        }
    }

    ~TlsTransport() override {
        if (ssl_ != nullptr) api_->ssl_free(ssl_);
        // The Socket never closes a transport's fd (ICI links own their
        // event fds); the raw TCP fd under TLS is ours.
        if (fd_ >= 0) ::close(fd_);
    }

    int event_fd() const override { return fd_; }
    bool Established() const override { return established_; }

    std::string alpn() const {
        const unsigned char* p = nullptr;
        unsigned len = 0;
        api_->get0_alpn_selected(ssl_, &p, &len);
        return p != nullptr ? std::string((const char*)p, len)
                            : std::string();
    }

    ssize_t CutFromIOBufList(IOBuf* const* pieces, size_t count,
                             int64_t*) override {
        // Chaos: faults on the PLAINTEXT side of the record layer, so a
        // corrupt byte arrives MAC-valid and only the application-level
        // crc32c can catch it (exactly the property under test).
        // Decided (and slept) BEFORE taking ssl_mu_: fiber_usleep may
        // resume on another worker thread, and unlocking a std::mutex
        // from a non-owner thread is UB (pieces are owned by the single
        // elected writer, so touching them here is safe).
        FaultAction fault;
        size_t fault_budget = 0;  // kShort: plaintext bytes still allowed
        if (__builtin_expect(fault_injection_enabled(), 0)) {
            size_t total_len = 0;
            for (size_t i = 0; i < count; ++i) total_len += pieces[i]->size();
            fault = FaultInjection::Decide(FaultOp::kWrite, remote_,
                                           total_len);
            switch (fault.kind) {
                case FaultAction::kReset:
                    errno = ECONNRESET;
                    return -1;
                case FaultAction::kDelay:
                    // Safe to park: with chaos enabled, Socket::FlushOnce
                    // routes every write through the KeepWrite fiber
                    // (no caller locks on that stack).
                    fiber_usleep(fault.delay_us);
                    break;
                case FaultAction::kDrop: {
                    for (size_t i = 0; i < count; ++i) {
                        pieces[i]->pop_front(pieces[i]->size());
                    }
                    return (ssize_t)total_len;
                }
                case FaultAction::kShort:
                    fault_budget = fault.max_bytes > 0 ? fault.max_bytes : 1;
                    break;
                default:
                    break;
            }
        }
        // SSL* is not thread-safe; the KeepWrite fiber and the input
        // fiber (Pump) can run concurrently.
        std::lock_guard<std::mutex> g(ssl_mu_);
        if (!DriveHandshake()) return -1;  // errno set
        ssize_t total = 0;
        char chunk[16384];
        for (size_t i = 0; i < count; ++i) {
            IOBuf* piece = pieces[i];
            while (!piece->empty()) {
                size_t n = piece->copy_to(chunk, sizeof(chunk));
                if (fault.kind == FaultAction::kShort) {
                    if (fault_budget == 0) {
                        // Short write: report what went through (or
                        // EAGAIN so the writer parks and retries).
                        if (total > 0) return total;
                        errno = EAGAIN;
                        return -1;
                    }
                    n = std::min(n, fault_budget);
                }
                if (fault.kind == FaultAction::kCorrupt && total == 0) {
                    chunk[fault.aux % n] ^= 0x20;
                }
                api_->err_clear();  // see WantMore()
                const int w = api_->ssl_write(ssl_, chunk, (int)n);
                if (w <= 0) {
                    if (WantMore(w)) {
                        errno = EAGAIN;
                        return total > 0 ? total : -1;
                    }
                    errno = EIO;
                    return total > 0 ? total : -1;
                }
                piece->pop_front((size_t)w);
                total += w;
                if (fault.kind == FaultAction::kShort) {
                    fault_budget -= std::min(fault_budget, (size_t)w);
                }
            }
        }
        return total;
    }

    int WaitWritable(int64_t abstime_us) override {
        // Wait for the direction the last SSL op actually needed: a
        // handshake stalled on WANT_READ must NOT poll POLLOUT (a TCP
        // socket is almost always write-ready — that poll returns
        // immediately and the KeepWrite loop busy-spins for the whole
        // handshake RTT).
        const short ev = want_events_.load(std::memory_order_acquire);
        pollfd p{fd_, ev != 0 ? ev : (short)(POLLIN | POLLOUT), 0};
        int timeout_ms = 100;
        if (abstime_us > 0) {
            const int64_t remain_ms =
                (abstime_us - monotonic_time_us()) / 1000;
            if (remain_ms <= 0) return -1;
            timeout_ms = (int)std::min<int64_t>(remain_ms, 100);
        }
        return ::poll(&p, 1, timeout_ms) >= 0 ? 0 : -1;
    }

    ssize_t Pump(IOPortal* dst, PumpStamps*) override {
        // Chaos: inbound faults on the decrypted plaintext. Decided (and
        // slept) BEFORE ssl_mu_ — see CutFromIOBufList.
        FaultAction fault;
        if (__builtin_expect(fault_injection_enabled(), 0)) {
            fault = FaultInjection::Decide(FaultOp::kRead, remote_, 16384);
            if (fault.kind == FaultAction::kReset) {
                errno = ECONNRESET;
                return -1;
            }
            if (fault.kind == FaultAction::kDelay) {
                fiber_usleep(fault.delay_us);
            }
        }
        std::lock_guard<std::mutex> g(ssl_mu_);
        if (!DriveHandshake()) return -1;
        ssize_t total = 0;
        char buf[16384];
        while (true) {
            api_->err_clear();  // see WantMore()
            int want = sizeof(buf);
            if (fault.kind == FaultAction::kShort) {
                want = (int)std::min<size_t>(
                    sizeof(buf), fault.max_bytes > 0 ? fault.max_bytes : 1);
            }
            const int r = api_->ssl_read(ssl_, buf, want);
            if (r > 0) {
                if (fault.kind == FaultAction::kCorrupt && total == 0) {
                    buf[fault.aux % (uint64_t)r] ^= 0x20;
                }
                if (fault.kind != FaultAction::kDrop) {
                    dst->append(buf, (size_t)r);
                }
                total += r;
                if (fault.kind == FaultAction::kShort) return total;
                continue;
            }
            const int err = api_->get_error(ssl_, r);
            if (err == kSslErrorZeroReturn) {
                return total > 0 ? total : 0;  // clean TLS shutdown
            }
            if (err == kSslErrorWantRead || err == kSslErrorWantWrite) {
                if (total > 0) return total;
                errno = EAGAIN;
                return -1;
            }
            // Transport error; a half-read burst still delivers.
            if (total > 0) return total;
            return 0;  // treat as EOF: the socket fails via TERR_EOF
        }
    }

    void Close() override {
        std::lock_guard<std::mutex> g(ssl_mu_);
        if (!closed_) {
            closed_ = true;
            api_->err_clear();
            api_->ssl_shutdown(ssl_);
            // Leave the queue clean: shutdown of an in-handshake session
            // records an error the next connection on this thread must
            // not inherit.
            api_->err_clear();
        }
    }

    void Release() override { delete this; }

private:
    // SSL_get_error consults the THREAD-LOCAL OpenSSL error queue: a
    // stale entry left by another connection on this thread (e.g. its
    // teardown SSL_shutdown) makes an innocent EAGAIN read classify as
    // fatal SSL_ERROR_SSL. Every SSL op here is preceded by
    // ERR_clear_error() so the queue only ever holds THIS call's errors.
    bool WantMore(int rc) {
        const int err = api_->get_error(ssl_, rc);
        if (err == kSslErrorWantRead) {
            want_events_.store(POLLIN, std::memory_order_release);
            return true;
        }
        if (err == kSslErrorWantWrite) {
            want_events_.store(POLLOUT, std::memory_order_release);
            return true;
        }
        return false;
    }

    // Returns true once established; false with errno=EAGAIN while the
    // handshake still needs bytes, errno=EIO on fatal failure.
    bool DriveHandshake() {
        if (established_) return true;
        api_->err_clear();
        const int rc = api_->do_handshake(ssl_);
        if (rc == 1) {
            established_ = true;
            return true;
        }
        errno = WantMore(rc) ? EAGAIN : EIO;
        return false;
    }

    SSL* ssl_;
    int fd_;
    SslApi* api_;
    EndPoint remote_;  // per-peer fault-injection scoping
    std::mutex ssl_mu_;
    std::atomic<short> want_events_{0};  // POLLIN/POLLOUT of last WANT_*
    bool established_ = false;
    bool closed_ = false;
};

}  // namespace

bool TlsAvailable() { return ssl_api() != nullptr; }

int TlsServerInit(const std::string& cert_pem_path,
                  const std::string& key_pem_path) {
    SslApi* a = ssl_api();
    if (a == nullptr) {
        LOG(ERROR) << "TLS requested but libssl is not available";
        return -1;
    }
    static std::mutex mu;
    std::lock_guard<std::mutex> g(mu);
    if (g_server_ctx != nullptr) return 0;
    SSL_CTX* ctx = a->ctx_new(a->tls_method());
    if (ctx == nullptr) return -1;
    if (a->use_cert_chain(ctx, cert_pem_path.c_str()) != 1 ||
        a->use_privkey(ctx, key_pem_path.c_str(), kSslFiletypePem) != 1) {
        LOG(ERROR) << "TLS: failed to load cert/key from "
                   << cert_pem_path << " / " << key_pem_path;
        a->ctx_free(ctx);
        return -1;
    }
    a->ctx_ctrl(ctx, kSslCtrlMode, kModePartialWrite | kModeMovingBuffer,
                nullptr);
    a->ctx_set_alpn_select_cb(ctx, AlpnSelect, nullptr);
    g_server_ctx = ctx;
    return 0;
}

TransportEndpoint* NewTlsServerTransport(int fd) {
    SslApi* a = ssl_api();
    if (a == nullptr || g_server_ctx == nullptr) return nullptr;
    SSL* ssl = a->ssl_new(g_server_ctx);
    if (ssl == nullptr) return nullptr;
    a->set_fd(ssl, fd);
    a->set_accept_state(ssl);
    return new TlsTransport(ssl, fd, a);
}

TransportEndpoint* NewTlsClientTransport(int fd, const std::string& alpn,
                                         const std::string& sni) {
    SslApi* a = ssl_api();
    SSL_CTX* ctx = client_ctx();
    if (a == nullptr || ctx == nullptr) return nullptr;
    SSL* ssl = a->ssl_new(ctx);
    if (ssl == nullptr) return nullptr;
    a->set_fd(ssl, fd);
    a->set_connect_state(ssl);
    if (!alpn.empty()) {
        // ALPN wire format: length-prefixed protocol list.
        std::string wire;
        wire.push_back((char)alpn.size());
        wire += alpn;
        a->set_alpn_protos(ssl, (const unsigned char*)wire.data(),
                           (unsigned)wire.size());
    }
    if (!sni.empty()) {
        a->ssl_ctrl(ssl, kCtrlSetTlsextHostname, kTlsextNametypeHost,
                    (void*)sni.c_str());
    }
    return new TlsTransport(ssl, fd, a);
}

}  // namespace tpurpc
