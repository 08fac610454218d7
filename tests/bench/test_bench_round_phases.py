"""The readers of the engine's round-phase counters (host_serial_ms and
dispatch_ms) on hand-built contexts and on the counters
of a tiny engine run, and the trace reduction naming an idle gap by the
engine's innermost span."""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, xplane  # noqa: E402
from bench.xplane import Device, Trace  # noqa: E402

READERS = ["host_serial_ms.offline", "dispatch_ms.offline"]
C0 = {"serve_fused_rounds_total": 100.0,
      "serve_dispatch_seconds_total": 0.05,
      "serve_fetch_seconds_total": 4.0,
      "serve_host_serial_seconds_total": 0.04,
      "serve_admit_seconds_total": 0.5,
      "serve_stall_dispatch_seconds_total": 0.0,
      "serve_stall_fetch_seconds_total": 0.0,
      "serve_stall_host_seconds_total": 0.0}
# 1000 rounds: 0.8 s dispatching (0.3 s of it stalled), 48 s fetching
# (0.5 s stalled), 1.4 s of host serial work (1.0 s stalled) and 0.8 s
# admitting, over a 51 s window
C1 = {"serve_fused_rounds_total": 1100.0,
      "serve_dispatch_seconds_total": 0.85,
      "serve_fetch_seconds_total": 52.0,
      "serve_host_serial_seconds_total": 1.44,
      "serve_admit_seconds_total": 1.3,
      "serve_stall_dispatch_seconds_total": 0.3,
      "serve_stall_fetch_seconds_total": 0.5,
      "serve_stall_host_seconds_total": 1.0}
EXPECTED = {"host_serial_ms.offline": 0.4, "dispatch_ms.offline": 0.5}
NO_STALLS = {"host_serial_ms.offline": 1.4, "dispatch_ms.offline": 0.8}


def _ctx(c0, c1, window_s=51.0):
    return {"counters": (c0, c1), "window_s": window_s}


@pytest.mark.parametrize("name", READERS)
def test_reader_value(name):
    assert harness.reader(name)(_ctx(C0, C1)) == pytest.approx(
        EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_none_without_rounds(name):
    read = harness.reader(name)
    assert read(_ctx({}, {})) is None         # a program without counters
    assert read(_ctx(C0, dict(C0))) is None   # no round in the window


@pytest.mark.parametrize("name", READERS)
def test_missing_stall_counters_read_zero(name):
    drop = lambda c: {k: v for k, v in c.items() if "stall" not in k}
    assert harness.reader(name)(_ctx(drop(C0), drop(C1))) == \
        pytest.approx(NO_STALLS[name])


def test_readers_on_engine_counters(capsys):
    """The readers find the counters a serving run exports, and the four
    host phases fill the window they were read over."""
    sys.path.insert(0, str(ROOT / "tests"))
    from conftest import tiny_config, tiny_draft_config
    from repro.serving.engine import (SchedulerConfig, ServeRequest,
                                      ServingEngine)
    eng = ServingEngine(tiny_config(("attn",)), tiny_draft_config(),
                        config=SchedulerConfig(max_batch=2, n_cand=2))
    eng.init_from_seed(0)
    rng = np.random.default_rng(2)
    for i in range(10):
        eng.submit(ServeRequest(i, rng.integers(1, 61, 8).astype(np.int32),
                                max_new_tokens=int(rng.integers(8, 24))))
    eng.run_step()                            # compiles
    c0, w0 = harness.counters(eng), time.monotonic()
    while eng.has_work():
        eng.run_step()
    w1 = time.monotonic()
    ctx = _ctx(c0, harness.counters(eng), w1 - w0)
    values = {n: harness.reader(n)(ctx) for n in READERS}
    assert values["host_serial_ms.offline"] > 0
    assert values["dispatch_ms.offline"] > 0
    err = capsys.readouterr().err
    assert "host_serial_ms:" in err and "; stalls dispatch " in err
    share = float(err.split("window (")[1].split("%")[0])
    assert share == pytest.approx(100, abs=5)


def test_host_serial_line_splits_the_window(capsys):
    """The stderr line gives each host phase's seconds, their share of
    the window, and each round phase's stall excess."""
    harness.reader("host_serial_ms.offline")(_ctx(C0, C1))
    line = capsys.readouterr().err.strip()
    assert line.startswith("host_serial_ms: 1000 rounds; ")
    assert "dispatch 0.800s, fetch 48.000s, host_serial 1.400s, " \
        "admit 0.800s; sum 51.000s of a 51.000s window (100.00%)" in line
    assert line.endswith(
        "stalls dispatch 0.300s, fetch 0.500s, host 1.000s")


def test_gap_named_by_the_innermost_engine_span():
    # device ops [0, 40) and [46, 80); the idle gap [40, 46) has its
    # midpoint 43 inside d2h/outputs, which lies inside round/round
    ops = [("fusion.1", 0, 40), ("fusion.2", 46, 80)]
    host = [("bench/run_step", 0, 50), ("round/round", 1, 49),
            ("target_verify/verify(fused)", 1, 2),
            ("d2h/outputs", 3, 44), ("round/account", 44, 45),
            ("round/emit", 45, 47)]
    red = xplane.reduce(Trace([Device("/device:TPU:0", ops, [])], host),
                        (0, 80))
    assert red.gaps == {"d2h/outputs": (1, 6)}
    assert red.longest_gaps == [(6, "d2h/outputs")]
