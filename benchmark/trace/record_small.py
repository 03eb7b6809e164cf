"""Record the small device trace that `benchmark/tests` checks the reduction on.

    chiprun -- python3 benchmark/trace/record_small.py

Runs on the chip only. One jitted step (the program's attention dispatch at
SDXL's first attention level, its GroupNorm at the one site the fused kernel
admits at SDXL 1024^2, and a matmul) is called a few times with the host
asleep between calls, so the trace holds known kernels and known idle gaps.
Writes `chiprun_out/small_trace/small.xplane.pb` and a text listing of its
planes, lines and first events; the committed copy is
`benchmark/trace/small.xplane.pb`.
"""

from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))


def describe(path: Path, out) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}", file=out)
        for line in lines:
            events = list(line.events)
            print(f"  LINE {line.name!r} events={len(events)}", file=out)
            for event in events[:12]:
                stats = {k: (v if not isinstance(v, (bytes, str))
                             or len(v) < 160 else str(v)[:160] + "...")
                         for k, v in event.stats}
                print(f"    {event.name!r} start_ns={event.start_ns} "
                      f"dur_ns={event.duration_ns} stats={stats}", file=out)


def main() -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_small.py needs a TPU", file=sys.stderr)
        return 3
    from benchmark.trace.capture import profile_options, xplane_files
    from chiaswarm_tpu.ops import dot_product_attention
    from chiaswarm_tpu.ops.group_norm import group_norm

    @jax.jit
    def probe_step(q, k, v, x, scale, bias, a, b):
        attn = dot_product_attention(q, k, v)
        norm = group_norm(x, scale, bias, groups=32, act="silu")
        return attn, norm, a @ b

    keys = jax.random.split(jax.random.key(0), 6)
    q, k, v = (jax.random.normal(kk, (2, 4096, 10, 64), jnp.bfloat16)
               for kk in keys[:3])
    x = jax.random.normal(keys[3], (2, 32, 32, 640), jnp.bfloat16)
    a = jax.random.normal(keys[4], (2048, 2048), jnp.bfloat16)
    b = jax.random.normal(keys[5], (2048, 2048), jnp.bfloat16)
    args = (q, k, v, x, jnp.ones((640,)), jnp.zeros((640,)), a, b)
    jax.block_until_ready(probe_step(*args))

    out_dir = REPO / "chiprun_out" / "small_trace"
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    log_dir = out_dir / "log"
    jax.profiler.start_trace(str(log_dir), profiler_options=profile_options())
    wall0 = time.time()
    with jax.profiler.TraceAnnotation(f"bench_sync wall={wall0:.6f}"):
        time.sleep(0.002)
    for _ in range(3):
        jax.block_until_ready(probe_step(*args))
        time.sleep(0.01)
    jax.profiler.stop_trace()

    traces = xplane_files(log_dir)
    if not traces:
        print("no .xplane.pb written", file=sys.stderr)
        return 1
    shutil.copy(traces[0], out_dir / "small.xplane.pb")
    with open(out_dir / "listing.txt", "w") as listing:
        describe(traces[0], listing)
    shutil.rmtree(log_dir)
    print((out_dir / "listing.txt").read_text()[-6000:])
    print("bytes", (out_dir / "small.xplane.pb").stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main())
