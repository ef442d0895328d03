// Synchronous echo client (reference example/echo_c++/client.cpp):
//   echo_client HOST:PORT [count]
#include <cstdio>
#include <cstdlib>
#include <string>

#include "bench_echo.pb.h"
#include "tbase/time.h"
#include "trpc/channel.h"
#include "trpc/controller.h"

using namespace tpurpc;

int main(int argc, char** argv) {
    if (argc < 2) {
        fprintf(stderr, "usage: %s HOST:PORT [count]\n", argv[0]);
        return 2;
    }
    const int count = argc > 2 ? atoi(argv[2]) : 4;
    Channel channel;
    ChannelOptions options;
    options.timeout_ms = 1000;
    options.max_retry = 3;
    if (channel.Init(argv[1], &options) != 0) {
        fprintf(stderr, "bad address %s\n", argv[1]);
        return 1;
    }
    benchpb::EchoService_Stub stub(&channel);
    for (int i = 0; i < count; ++i) {
        Controller cntl;
        benchpb::EchoRequest request;
        benchpb::EchoResponse response;
        request.set_send_ts_us(monotonic_time_us());
        const std::string body = "hello tpu-rpc #" + std::to_string(i);
        cntl.request_attachment().append(body);
        stub.Echo(&cntl, &request, &response, nullptr);  // sync: done=null
        if (cntl.Failed()) {
            fprintf(stderr, "rpc %d failed: %s\n", i,
                    cntl.ErrorText().c_str());
            return 1;
        }
        if (cntl.response_attachment().to_string() != body) {
            fprintf(stderr, "rpc %d: reply bytes differ from the request\n",
                    i);
            return 1;
        }
        printf("echo %d: rtt=%lldus attachment=%zuB\n", i,
               (long long)(monotonic_time_us() - response.send_ts_us()),
               cntl.response_attachment().size());
    }
    return 0;
}
