#!/usr/bin/env python
"""Fetch a worker's /metrics and print the placement/batching table.

Two modes (mirroring tools/metrics_dump.py):

  python tools/placement_stats.py --url http://127.0.0.1:8061
      Scrape a LIVE worker's telemetry endpoint (Settings.metrics_port /
      CHIASWARM_METRICS_PORT) and print its dispatch-board placement
      outcomes (`swarm_placement_total{outcome}` -> affinity hit rate,
      steals, cold loads) and batch flush reasons
      (`swarm_batch_flush_total{reason}`, including "preempt").

  python tools/placement_stats.py
      No worker required: drive the REAL placement path in process — a
      2-slice SliceAllocator + BatchScheduler dispatch board through a
      cold -> affinity -> steal claim sequence (pipeline loads emulated
      via the residency map, exactly what registry builds record) — then
      print the same table from the process-local registry. Set
      JAX_PLATFORMS=cpu to keep it off an attached chip.

What the table answers: is residency routing working (high affinity hit
rate at steady state), how often slices steal foreign groups instead of
idling, and how often interactive jobs preempted lingering groups.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys

# reuse the battle-tested Prometheus exposition parser
try:
    from metrics_dump import fetch, parse_metrics
except ImportError:  # direct script invocation: tools/ not on sys.path
    import pathlib

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    from metrics_dump import fetch, parse_metrics

PLACEMENT_METRIC = "swarm_placement_total"
FLUSH_METRIC = "swarm_batch_flush_total"
OUTCOMES = ("affinity", "steal", "cold")


def placement_summary(samples: list[tuple[str, dict, float]]) -> dict:
    """Exposition samples -> {outcome counts, affinity_hit_rate, steals,
    flush reasons}."""
    outcomes = {o: 0 for o in OUTCOMES}
    flushes: dict[str, int] = {}
    for name, labels, value in samples:
        if name == PLACEMENT_METRIC and labels.get("outcome") in outcomes:
            outcomes[labels["outcome"]] = int(value)
        elif name == FLUSH_METRIC and "reason" in labels:
            flushes[labels["reason"]] = int(value)
    claimed = sum(outcomes.values())
    return {
        "placements": outcomes,
        "claimed": claimed,
        "affinity_hit_rate": (
            round(outcomes["affinity"] / claimed, 3) if claimed else None
        ),
        "steals": outcomes["steal"],
        "flushes": dict(sorted(flushes.items())),
    }


def render(summary: dict) -> str:
    if not summary["claimed"]:
        return "(no placements recorded yet — has a work item dispatched?)"
    lines = [
        f"{'outcome':<10} {'count':>7}",
        "-" * 18,
    ]
    for outcome in OUTCOMES:
        lines.append(f"{outcome:<10} {summary['placements'][outcome]:>7}")
    lines.append("-" * 18)
    lines.append(f"{'claimed':<10} {summary['claimed']:>7}")
    rate = summary["affinity_hit_rate"]
    lines.append(f"affinity_hit_rate: {rate if rate is not None else '-'}")
    lines.append(f"steals: {summary['steals']}")
    if summary["flushes"]:
        lines.append("")
        lines.append(f"{'flush reason':<12} {'count':>7}")
        lines.append("-" * 20)
        for reason, count in summary["flushes"].items():
            lines.append(f"{reason:<12} {count:>7}")
    return "\n".join(lines)


async def _inprocess_claims() -> list[str]:
    """Drive the real dispatch board through cold -> affinity -> steal on
    a 2-slice allocator; returns the claim outcome sequence."""
    from chiaswarm_tpu.batching import BatchScheduler
    from chiaswarm_tpu.chips import allocator as alloc_mod
    from chiaswarm_tpu.chips.allocator import SliceAllocator

    import jax

    # known-empty residency so the cold -> affinity -> steal choreography
    # is deterministic even in a process that already served jobs
    alloc_mod.reset_residency()
    devices = jax.devices()
    # two slices even on a single-device host: the smoke exercises claim
    # mechanics only, never executes on the slices
    if len(devices) >= 2:
        alloc = SliceAllocator(devices=devices[: len(devices) // 2 * 2],
                               chips_per_job=len(devices) // 2)
    else:
        alloc = SliceAllocator(devices=devices * 2, chips_per_job=1)
    sched = BatchScheduler(linger_s=0.005, max_coalesce=8,
                           free_slices=lambda: alloc.free_count)
    alloc.add_free_listener(sched.notify)

    def job(i: int, steps: int = 2) -> dict:
        return {"id": f"stats-{i}", "workflow": "txt2img",
                "model_name": "test/tiny-sd", "prompt": f"probe {i}",
                "height": 64, "width": 64, "num_inference_steps": steps,
                "parameters": {}}

    outcomes = []
    await sched.put(job(0))
    _, cs, outcome = await asyncio.wait_for(sched.claim(alloc), 5.0)
    outcomes.append(outcome)
    alloc_mod.note_resident("test/tiny-sd", cs.slice_id)  # the load event
    alloc.release(cs)

    await sched.put(job(1))
    _, held, outcome = await asyncio.wait_for(sched.claim(alloc), 5.0)
    outcomes.append(outcome)

    await sched.put(job(2, steps=3))  # home busy -> idle slice steals
    _, cs3, outcome = await asyncio.wait_for(sched.claim(alloc), 5.0)
    outcomes.append(outcome)
    alloc.release(held)
    alloc.release(cs3)
    return outcomes


def run_inprocess() -> str:
    from chiaswarm_tpu.telemetry import REGISTRY

    outcomes = asyncio.run(_inprocess_claims())
    print(f"claim sequence: {' -> '.join(outcomes)}")
    return REGISTRY.render()


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="placement_stats", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument(
        "--url", default=None,
        help="live worker telemetry base URL (e.g. http://127.0.0.1:8061); "
             "omit to run the in-process placement smoke instead")
    parser.add_argument(
        "--raw", action="store_true",
        help="also dump the raw /metrics exposition text")
    parser.add_argument(
        "--json", action="store_true", dest="as_json",
        help="print the summary as one JSON object instead of a table")
    args = parser.parse_args(argv)

    if args.url:
        text = fetch(args.url, "/metrics")
    else:
        text = run_inprocess()

    if args.raw:
        print(text)
    summary = placement_summary(parse_metrics(text))
    if args.as_json:
        print(json.dumps(summary, indent=1))
    else:
        print(render(summary))
    return 0 if summary["claimed"] else 1


if __name__ == "__main__":
    sys.exit(main())
