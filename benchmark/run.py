#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip: it stands the swarm up, warms it,
measures for `--seconds`, checks the outputs, and prints as its last line
one JSON object (`correct`, `attempted`, `failed`, `metrics`, `device`, with
`--trace 1` `breakdown`, and last `compared`: every number `correct`
compared beside its limit, which are also the last lines of stderr).
Earlier lines are one JSON object each.
Fails (no result, exit code other than 0) without a TPU, with another
number of chips than the cell asks for, or outside a whole checkout.
`benchmark/rehearse.py` is the CPU rehearsal; this command never falls
back to it.
"""

from __future__ import annotations

import time

_STARTED = time.monotonic()  # set-up is measured from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))


def parse(argv):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return parser.parse_args(argv)


def main(argv=None, platform: str = "tpu", rehearsal: bool = False) -> int:
    args = parse(argv)
    from benchmark import harness

    try:
        if not (REPO / "chiaswarm_tpu").is_dir():
            raise harness.RunFailure(
                f"{REPO} holds no chiaswarm_tpu/: the benchmark measures "
                "the program of its own checkout")
        spec = harness.load_cell(args.workload)
        if rehearsal:
            harness.apply_rehearsal(spec)
        harness.set_deployment(spec["config"])
        import jax

        devices = jax.devices()
        found = {"platform": devices[0].platform,
                 "kind": devices[0].device_kind, "count": len(devices)}
        if found["platform"] != platform \
                or found["count"] != spec["cell"]["chips"]:
            raise harness.RunFailure(
                f"cell {args.workload} needs {spec['cell']['chips']} "
                f"{platform} chip(s); jax found {found}")
        # the program prints as it works; the result lines are ours alone
        with contextlib.redirect_stdout(sys.stderr):
            record = asyncio.run(harness.run_cell(
                spec, args.seed, args.seconds, bool(args.trace), _STARTED,
                rehearsal=rehearsal))
            record["device"] = found
            result = harness.report(record, bool(args.trace))
    except harness.RunFailure as failure:
        print(f"benchmark: {failure}", file=sys.stderr)
        return 3
    for name, (number, limit) in result["compared"].items():
        print(f"compared {name}: {number} limit {limit}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
