"""The trace reduction on the small trace recorded on a v5e
(`trace/record_small.py`): three calls of one jitted step (flash attention
at 2x4096x10x64, fused GroupNorm at 2x32x32x640, a 2048^3 matmul) with the
host asleep 10 ms between them."""

from pathlib import Path

import pytest

from benchmark.trace import reduce

TRACE = Path(__file__).resolve().parents[1] / "trace" / "small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return reduce.reduce_trace(
        TRACE, kernels=("flash_attention", "fused_group_norm"))


def test_busy_and_idle_are_the_known_ones(reduced):
    assert reduced["chips"] == 1
    assert reduced["window_s"] == pytest.approx(0.030682764, rel=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.0069726, rel=1e-6)
    assert reduced["idle_share"] == pytest.approx(0.77275, abs=1e-5)


def test_busy_agrees_with_a_brute_force_timeline(reduced):
    """Independent of `union`: paint every event onto a 10 ns grid."""
    events = next(iter(reduce.device_events(reduce.load(TRACE)).values()))
    lo, hi = reduced["stretch_ns"]
    cells = bytearray(int((hi - lo) / 10) + 1)
    for _, start, duration in events:
        if duration > 0:
            a, b = int((start - lo) / 10), int((start + duration - lo) / 10)
            cells[a:b] = b"\x01" * (b - a)
    assert sum(cells) * 10 / 1e9 == pytest.approx(reduced["busy_s"], rel=2e-3)


def test_kernel_sums_and_shapes(reduced):
    flash = reduced["kernel_calls"]["flash_attention"]
    norm = reduced["kernel_calls"]["fused_group_norm"]
    assert len(flash) == 3 and len(norm) == 3
    assert sum(c["seconds"] for c in flash) == pytest.approx(
        0.006439794, rel=1e-6)
    assert sum(c["seconds"] for c in norm) == pytest.approx(2.6005e-05,
                                                            rel=1e-4)
    assert reduced["op_seconds"]["flash_attention"] == pytest.approx(
        0.006439794, rel=1e-6)
    # result, then q, k, v as the kernel sees them: [B, H, S, D]
    assert flash[0]["shapes"][:4] == [(2, 10, 4096, 64)] * 4
    assert norm[0]["shapes"][0] == (2, 1024, 640)


def test_the_two_sleeps_are_the_longest_gaps(reduced):
    longest = [(b - a) / 1e6 for a, b in reduced["gaps_ns"][:3]]
    assert longest[0] == pytest.approx(11.97, abs=0.01)
    assert longest[1] == pytest.approx(11.74, abs=0.01)
    assert longest[2] < 0.01


def test_annotation_gives_the_clock(reduced):
    from benchmark import breakdown

    to_wall = breakdown.clock(reduced)
    name, start_ns, _ = reduced["annotations"][0]
    assert name.startswith("bench_sync wall=")
    assert to_wall(start_ns + 1e9) == pytest.approx(
        float(name.split("=")[1]) + 1.0)


def test_names_and_shapes_from_instruction_text():
    text = ('%flash_attention.1 = bf16[2,10,4096,64]{3,2,1,0:T(8,128)(2,1)} '
            'custom-call(bf16[2,10,4096,64]{3,2,1,0} %q, bf16[2,10,128,64] %k)')
    assert reduce.op_name(text) == "flash_attention"
    assert reduce.op_name("%fusion.12.3 = f32[] fusion()") == "fusion"
    assert reduce.shapes_in(text) == [(2, 10, 4096, 64), (2, 10, 4096, 64),
                                      (2, 10, 128, 64)]


def test_union_and_clip():
    assert reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert reduce.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
