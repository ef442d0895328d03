#!/usr/bin/env python3
"""Does the stage clock account for what a caller waits?

Runs the program's own cross-process sweep (`build/echo_bench --xproc
--scale`: a client process and an `--ici-server` child over the shm link,
4 KiB sync echoes from 1, 4, 16 and 64 caller fibers) and, for one level
(default 16 callers), sets the chain of stage means

    trpc.issue + tnet.write_queue                         (client)
    + tici.link_handoff          (request; the server's pump records it)
    + the server's five stages from consume to post       (server)
    + tici.link_handoff          (reply; the client's pump records it)
    + tnet.consume_to_cut + trpc.match + trpc.caller_wake     (client)

beside the mean latency the callers observed themselves, and says what
share is unexplained. The client's half comes from the `STAGES` lines
echo_bench prints on stderr at each edge of a level (its cumulative
tpurpc_stage_dump), the server's from the child's `/status?format=json`
scraped as those lines arrive; both are differenced over the level.

Each leg ends on the clock read the next starts from, in both processes
(same host, same CLOCK_MONOTONIC), so the chain has no overlap; the
caller's own clock adds only CallMethod's first lines and its return.
tfiber.wake_to_run is NOT in the chain: it samples every fiber made
runnable, and the input fiber started for a doorbell waits inside the
link hand-off already counted. It is printed beside the chain, as are
the server's stages. tici.link_handoff has one sample a descriptor (4-6
to a 4 KiB message, posted and consumed together): its mean stands for
the message's.

    python3 tools/stage_closure.py [callers]     # prints a table + 1 JSON line
"""
import json
import re
import subprocess
import sys
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
MARK = re.compile(r"^STAGES (begin|end) callers=(\d+) calls=(\d+) "
                  r"caller_sum_us=(\d+) park_timeouts_found_work=(\S+) "
                  r"stages=(\{.*\})$")


def scrape(port: int) -> dict:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/status?format=json", timeout=10) as r:
        status = json.loads(r.read())
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/vars", timeout=10) as r:
        found = {m.group(1): int(m.group(2)) for m in re.finditer(
            r"^(\w+_found_work) : (\d+)\b", r.read().decode(), re.M)}
    return {"stages": status["stages"], "found_work": found}


def window(before: dict, after: dict, stage: str):
    n = after[stage]["count"] - before[stage]["count"]
    s = after[stage]["sum_us"] - before[stage]["sum_us"]
    return n, (s / n if n else None)


RESIDENCE = ("tnet.consume_to_cut", "tfiber.dispatch_to_handler",
             "trpc.handler", "trpc.respond", "tnet.write_queue")


def main() -> int:
    level = int(sys.argv[1]) if len(sys.argv) > 1 else 16
    proc = subprocess.Popen(
        [str(REPO / "build" / "echo_bench"), "--xproc", "--scale", "--json"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    port, client, server = None, {}, {}
    for line in proc.stderr:
        line = line.strip()
        if line.startswith("XPROC_SERVER_PORT "):
            port = int(line.split()[1])
            continue
        m = MARK.match(line)
        if m is None or int(m.group(2)) != level:
            continue
        edge = m.group(1)
        server[edge] = scrape(port)  # as close to the mark as a scrape gets
        client[edge] = {"calls": int(m.group(3)),
                        "caller_sum_us": int(m.group(4)),
                        "found_work": int(float(m.group(5))),
                        "stages": json.loads(m.group(6))}
    result = proc.stdout.read().strip()
    if proc.wait(timeout=60) != 0 or set(client) != {"begin", "end"}:
        print(f"echo_bench rc {proc.returncode}, marks {sorted(client)}: "
              f"no closure", file=sys.stderr)
        return 1
    calls = client["end"]["calls"]
    observed = client["end"]["caller_sum_us"] / calls
    cb, ce = client["begin"]["stages"], client["end"]["stages"]
    sb, se = server["begin"]["stages"], server["end"]["stages"]
    inside = [(name, window(sb, se, name)) for name in RESIDENCE]
    residence = (min(n for _, (n, _) in inside),
                 None if any(m is None for _, (_, m) in inside)
                 else sum(m for _, (_, m) in inside))
    chain = [
        ("client trpc.issue", window(cb, ce, "trpc.issue")),
        ("client tnet.write_queue", window(cb, ce, "tnet.write_queue")),
        ("server tici.link_handoff (request)",
         window(sb, se, "tici.link_handoff")),
        ("server residence (its five stages)", residence),
        ("client tici.link_handoff (reply)",
         window(cb, ce, "tici.link_handoff")),
        ("client tnet.consume_to_cut", window(cb, ce, "tnet.consume_to_cut")),
        ("client trpc.match", window(cb, ce, "trpc.match")),
        ("client trpc.caller_wake", window(cb, ce, "trpc.caller_wake")),
    ]
    beside = [("client tfiber.wake_to_run",
               window(cb, ce, "tfiber.wake_to_run")),
              ("server tfiber.wake_to_run",
               window(sb, se, "tfiber.wake_to_run"))]
    total = sum(mean for _, (_, mean) in chain if mean is not None)
    print(f"{level} callers x 4 KiB, {calls} calls: callers observed a mean "
          f"of {observed:.2f} us")
    for name, (n, mean) in chain:
        print(f"  {name:36s} n={n:8d}  mean "
              + (f"{mean:9.2f} us" if mean is not None else "     none"))
    print(f"  {'chain':36s} {'':10s}  sum  {total:9.2f} us = "
          f"{100 * total / observed:.1f} % of observed; unexplained "
          f"{observed - total:.2f} us ({100 * (1 - total / observed):.1f} %)")
    print("  inside the server's residence:")
    for name, (n, mean) in inside:
        print(f"    {name:34s} n={n:8d}  mean "
              + (f"{mean:9.2f} us" if mean is not None else "     none"))
    print("  beside the chain (every fiber made runnable -> running; the "
          "input fiber's wait lies inside the link hand-off):")
    for name, (n, mean) in beside:
        print(f"    {name:34s} n={n:8d}  mean "
              + (f"{mean:9.2f} us" if mean is not None else "     none"))
    rescued = {
        "client": client["end"]["found_work"] - client["begin"]["found_work"],
        "server": {k: v - server["begin"]["found_work"].get(k, 0)
                   for k, v in server["end"]["found_work"].items()}}
    print(json.dumps({
        "callers": level, "calls": calls, "observed_mean_us": observed,
        "chain_us": {name: mean for name, (_, mean) in chain},
        "chain_sum_us": total,
        "unexplained_share": 1 - total / observed,
        "server_inside_us": {name: mean for name, (_, mean) in inside},
        "beside_us": {name: mean for name, (_, mean) in beside},
        "rescued_wakeups": rescued, "echo_bench": json.loads(result)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
