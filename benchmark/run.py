#!/usr/bin/env python3
"""Run ONE cell of BENCHMARK.json once and print the contract's last line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1>

The cell names a configuration and a traffic mix (both data files); the
configuration names one of the drivers in `benchmark/drivers/`. This
process is the only one that touches JAX; what it spawns is host-only,
has a hard timeout and is stopped before exit. A run that finds no TPU
exits non-zero with no result line. `--rehearsal 1` (with `--set k=v`
overrides of the traffic's sizes) is the CPU rehearsal the tests make:
it requires the CPU platform and the line it prints names the CPU.
`--control <name>` swaps in a deliberately broken path (see README):
the benchmark's own runs never pass it.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmark import device, manifest, xplane  # noqa: E402


@dataclass
class Run:
    """What a driver is handed."""
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    control: str | None
    devices: list
    notes: dict = field(default_factory=dict)

    def mark(self, phase: str) -> None:
        """Note when a set-up phase ended, in seconds since the start."""
        self.notes.setdefault("setup_marks_s", {})[phase] = round(
            time.monotonic() - T_START, 3)


def process_age_s() -> float:
    """Seconds this process had lived when this module began to run
    (interpreter start-up), so `setup_s` starts at the process's start."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return max(0.0, age - (time.monotonic() - T_START))
    except (OSError, ValueError, IndexError):
        return 0.0


def parse_overrides(pairs) -> dict:
    out = {}
    for pair in pairs:
        key, _, value = pair.partition("=")
        out[key] = json.loads(value)
    return out


def result_line(man, cell, obs, trace, setup_s, device,
                not_committed=None) -> dict:
    """The last line: the cell's end-to-end metrics (--trace 0) or its
    per-layer metrics (--trace 1), `compared` last. `not_committed` marks
    a rehearsal, a control or overridden sizes, so such a line is never
    mistaken for a measured run of the cell as committed."""
    metrics = {}
    if not trace:
        values = dict(obs["end_to_end"], setup_s=setup_s)
        for m in manifest.metrics_of(man, "end_to_end", cell["name"]):
            if m["name"] not in values:
                raise manifest.ManifestError(
                    f"driver gave no {m['name']} for cell {cell['name']}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        for m in manifest.metrics_of(man, "per_layer", cell["name"]):
            value = manifest.reader(m["name"]).read(obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    compared = {name: {"value": value, "limit": limit}
                for name, value, limit in obs["checks"]}
    correct = obs["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in compared.values())
    line = {"correct": correct, "attempted": obs["attempted"],
            "failed": obs["failed"], "metrics": metrics, "device": device}
    if trace and obs.get("trace") is not None:
        line["breakdown"] = xplane.breakdown(obs["trace"],
                                             obs.get("gap_notes"))
    if not_committed:
        line["not_the_committed_cell"] = not_committed
    line["compared"] = compared
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--control", default=None)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    ap.add_argument("--set", action="append", default=[], metavar="K=V",
                    help="override one key of the traffic file "
                         "(rehearsal and sweeps; the line says so)")
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed is a whole number >= 0 and --seconds is > 0")
    age = process_age_s()

    man = manifest.load()
    cell = manifest.cell(man, args.workload)
    config = manifest.config(man, cell)
    overrides = parse_overrides(args.set)
    traffic = dict(manifest.traffic(cell), **overrides)
    try:
        import brpc_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        sys.exit(f"benchmark: the program is not in this checkout ({e}); "
                 "no result")
    driver = importlib.import_module("benchmark.drivers." + config["driver"])

    devices = device.find_devices(cell["chips"], bool(args.rehearsal))
    run = Run(cell=cell, config=config, traffic=traffic, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace),
              control=args.control, devices=devices[:cell["chips"]])
    run.mark("device_found")
    obs = driver.run(run)

    setup_s = age + obs["t_first_op"] - T_START
    dev_info = {"platform": devices[0].platform,
                "kind": devices[0].device_kind, "count": len(devices),
                "memory_peak_bytes": obs["memory_peak_bytes"]}
    if args.trace:
        summary = obs.get("trace")
        dev_info["busy_s"] = summary["busy_s"] if summary else 0.0
        dev_info["window_s"] = (summary["window_s"] if summary
                              else obs["window_s"])
    not_committed = None
    if overrides or args.control or args.rehearsal:
        not_committed = {"set": overrides, "control": args.control,
                         "rehearsal": bool(args.rehearsal)}
    line = result_line(man, cell, obs, bool(args.trace), setup_s, dev_info,
                       not_committed)
    info = {"workload": cell["name"], "seed": args.seed,
            "seconds": args.seconds, "window_s": obs["window_s"],
            "setup_s": setup_s, "notes": run.notes}
    print(json.dumps(info), flush=True)
    for name, c in line["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {line['correct']}  attempted: {line['attempted']}  "
          f"failed: {line['failed']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
