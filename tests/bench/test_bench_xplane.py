"""The trace reduction (bench/xplane.py) on traces whose answers are known
by hand, and on a small trace recorded on a TPU v5e."""
import gzip
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import xplane  # noqa: E402
from bench.xplane import Device, Trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def _hand_trace():
    # device ops (ns): [0, 10) and [5, 20) overlap -> busy [0, 20);
    # [30, 40); [45, 50).  Window [0, 60): busy 20 + 10 + 5 = 35, idle
    # 25 in the gaps [20, 30), [40, 45), [50, 60).
    ops = [("fusion.1", 0, 10), ("paged_kernel", 5, 20),
           ("paged_kernel", 30, 40), ("copy.2", 45, 50)]
    modules = [("jit_fused_verify_and_draft(1)", 0, 20),
               ("jit_fused_verify_and_draft(1)", 30, 40),
               ("jit_rollback_draft(2)", 45, 50)]
    host = [("bench/run_step", 0, 29), ("round/round", 2, 26),
            ("admit/admit", 38, 47), ("bench/wait_arrival", 50, 70)]
    return Trace([Device("/device:TPU:0", ops, modules)], host)


def test_union_merges_and_clips():
    assert xplane.union([(5, 20), (0, 10), (30, 40), (45, 50), (55, 80)],
                        0, 60) == [(0, 20), (30, 40), (45, 50), (55, 60)]
    assert xplane.union([(70, 80)], 0, 60) == []


def test_reduce_hand_counts():
    red = xplane.reduce(_hand_trace(), (0, 60))
    assert red.window_ns == 60
    assert red.busy_ns == 35
    assert red.programs == {"jit_fused_verify_and_draft(1)": (2, 30),
                            "jit_rollback_draft(2)": (1, 5)}
    assert red.ops["paged_kernel"] == (2, 25)
    assert xplane.matching(red.programs, "fused_verify_and_draft") == (2, 30)
    assert xplane.matching(red.ops, "paged_kernel|copy") == (3, 30)
    # gap [20, 30) midpoint 25: innermost annotation round/round;
    # [40, 45) midpoint 42: admit/admit; [50, 60): bench/wait_arrival
    assert red.gaps == {"round/round": (1, 10), "admit/admit": (1, 5),
                        "bench/wait_arrival": (1, 10)}
    assert red.longest_gaps[:2] == [(10, "round/round"),
                                    (10, "bench/wait_arrival")]
    b = xplane.breakdown(red)
    assert b["device_ops"][0] == ["paged_kernel", 25e-9]
    assert len(b["device_ops"]) == 3
    assert sorted(b["idle_gaps"])[0] == ["admit/admit", 5e-9]


def test_reduce_averages_device_planes():
    t = _hand_trace()
    t.devices.append(Device("/device:TPU:1", [("fusion.9", 0, 60)], []))
    red = xplane.reduce(t, (0, 60))
    assert red.busy_ns == (35 + 60) / 2


def test_default_window_spans_the_ops():
    red = xplane.reduce(_hand_trace())
    assert red.window_ns == 50 and red.busy_ns == 35


PROTO = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 6000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "paged_kernel" } }
  event_metadata { key: 3 value { id: 3 name: "jit_fused_verify_and_draft" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2500000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 2600000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "bench/run_step" } }
  event_metadata { key: 2 value { id: 2 name: "PjitFunction(f)" } }
}
'''


def test_loader_reads_planes_lines_and_annotations():
    from jax.profiler import ProfileData
    tr = xplane.from_profile(ProfileData.from_text_proto(PROTO))
    assert [d.name for d in tr.devices] == ["/device:TPU:0"]
    assert tr.devices[0].ops == [("fusion.1", 1000, 3000),
                                 ("paged_kernel", 6000, 7000)]
    assert tr.devices[0].modules == [("jit_fused_verify_and_draft", 1000,
                                      7000)]
    assert tr.host == [("bench/run_step", 2500, 5500)]  # no JAX internals
    red = xplane.reduce(tr, (1000, 7000))
    assert red.busy_ns == 3000
    assert red.gaps == {"bench/run_step": (1, 3000)}


def _recorded():
    path = DATA / "offline_rounds.xplane.pb.gz"
    if not path.exists():
        pytest.skip("no recorded trace")
    from jax.profiler import ProfileData
    return xplane.from_profile(ProfileData.from_serialized_xspace(
        gzip.decompress(path.read_bytes())))


def test_recorded_chip_trace():
    """Three rounds of ``mixtral-8x7b.offline`` traced on one TPU v5e
    (``--seconds 0.6 --trace 1``): the reduction's numbers for that file."""
    tr = _recorded()
    assert [d.name for d in tr.devices] == ["/device:TPU:0"]
    span = [(s, e) for n, s, e in tr.host if n == "bench/traced_window"]
    assert len(span) == 1
    red = xplane.reduce(tr, span[0])
    assert red.window_ns == 171_137_058
    assert red.busy_ns == 169_800_987
    assert xplane.matching(red.programs, "fused_verify_and_draft") == \
        (3, 151_697_588)
    assert xplane.matching(red.programs, "rollback_draft") == (3, 18_106_042)
    # the paged verify kernel runs once per target layer (2) per round
    assert xplane.matching(red.ops, r"^%paged_decode_attention") == \
        (6, 30_482_397)
    assert red.longest_gaps[0] == (1_081_746, "round/round")
    b = xplane.breakdown(red)
    assert b["device_ops"][0][0].startswith("%paged_decode_attention")
    assert len(b["device_ops"]) == 10
