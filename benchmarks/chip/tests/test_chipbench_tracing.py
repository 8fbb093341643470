"""The readers of the program's tick spans and counters, on synthetic
`batching.TickRecord`s: each computes its number from the window's
ticks, and returns nothing from ticks that lack the fields (a program
without the spans)."""
import os
import sys
from collections import namedtuple
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as R  # noqa: E402
from repro.serving.batching import TickRecord  # noqa: E402

# a tick record as a program without spans and counters has it
BareTick = namedtuple("BareTick", "t seconds decode_tokens chunk_tokens "
                      "finalize_tokens oversized")
READERS = ("queue_wait_p50_ms", "host_ms_per_req", "xfer_kb_per_token", "compile_s")


def _view(ticks):
    served = SimpleNamespace(reqs=[], ticks=ticks, stats={}, t0=0.0, t1=10.0,
                             drained_at=10.0)
    return R.RunView({}, served, 10.0, 0.0, {}, None)


def _tick(**kw):
    base = dict(t=0.0, seconds=0.1, decode_tokens=0, chunk_tokens=0,
                finalize_tokens=0, oversized=False, wall_s=1.0)
    return TickRecord(**{**base, **kw})


TICKS = [
    _tick(chunk_tokens=256, h2d_bytes=600_000, d2h_bytes=400_000,
          phase_s=(("rcllm.engine.admit", 0.5), ("rcllm.engine.chunk.run", 0.2),
                   ("rcllm.engine.chunk.d2h", 0.1), ("rcllm.sched.tick", 0.05)),
          compile_s=(("jit(_jit_layer0_chunk)", 2.0),), waits=((7, 0.3),)),
    _tick(finalize_tokens=192, decode_tokens=1, h2d_bytes=1_500_000, d2h_bytes=500_000,
          phase_s=(("rcllm.engine.finalize.h2d", 0.25), ("rcllm.engine.finalize.run", 0.4),
                   ("rcllm.pool.write", 0.15), ("rcllm.server.step", 9.0)),
          compile_s=(("jit(_jit_selective_layers_kv) [cache]", 0.5),),
          waits=((8, 0.1), (9, 0.5)), n_finalized=1),
    _tick(decode_tokens=2, h2d_bytes=0, d2h_bytes=0),
]


def test_queue_wait_p50_ms_is_the_median_wait():
    assert R.load_reader("queue_wait_p50_ms")(_view(TICKS)) == pytest.approx(300.0)
    assert R.load_reader("queue_wait_p50_ms")(_view(TICKS[2:])) is None


def test_host_ms_per_req_leaves_out_device_waits_and_other_layers():
    # admit 0.5 + chunk.d2h 0.1 + finalize.h2d 0.25 + pool.write 0.15, one prefill
    assert R.load_reader("host_ms_per_req")(_view(TICKS)) == pytest.approx(1000.0)
    assert R.load_reader("host_ms_per_req")(_view(TICKS[:1])) is None


def test_xfer_kb_per_token_counts_both_ways_over_scheduled_tokens():
    # 3.0 MB over 256 chunk + 3 decode tokens (finalize charges are not tokens moved)
    assert R.load_reader("xfer_kb_per_token")(_view(TICKS)) == pytest.approx(
        3_000_000 / 259 / 1e3)


def test_compile_s_sums_every_function():
    assert R.load_reader("compile_s")(_view(TICKS)) == pytest.approx(2.5)
    assert R.load_reader("compile_s")(_view(TICKS[2:])) == 0.0


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_without_the_fields(name):
    bare = [BareTick(0.0, 0.1, 1, 64, 0, False), BareTick(0.1, 0.1, 1, 0, 192, False)]
    assert R.load_reader(name)(_view(bare)) is None
    assert R.load_reader(name)(_view([])) is None
