"""The trace reduction on a hand-made trace whose answers are known, and
on a small trace recorded on the v5e."""

from pathlib import Path

import pytest

from perfbench import tracereduce

HERE = Path(__file__).resolve().parent

# device: ops at [2,6) [4,7) us (overlap), [9,10) us on chip 0; the window is
# [1, 11) us, so busy = 5 + 1 = 6 us of 10 and the gaps are [1,2) [7,9) [10,11)
SYNTHETIC = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 9000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 11000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion = f32[64,100]{1,0} fusion(...)" } }
  event_metadata { key: 2 value { id: 2 name: "%custom-call = custom-call:TopK" } }
  event_metadata { key: 3 value { id: 3 name: "jit_batch_topk" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 7 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 7500000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "PjitFunction(batch_topk_scores_t)" } }
}
'''


@pytest.fixture()
def summary(monkeypatch):
    from jax.profiler import ProfileData

    monkeypatch.setattr(tracereduce, "MIN_GAP_NS", 500)
    return tracereduce.reduce_planes(
        ProfileData.from_text_proto(SYNTHETIC).planes
    )


def test_busy_union_counts_overlap_once(summary):
    assert summary.window_ns == 10_000
    assert summary.busy_ns == 6_000
    assert summary.idle_share == pytest.approx(0.4)
    assert summary.n_devices == 1


def test_per_op_sums_and_counts(summary):
    by_name = {name: (ns, n) for name, ns, n in summary.ops}
    assert by_name["%fusion = f32[64,100]{1,0} fusion(...)"] == (5_000, 2)
    assert by_name["%custom-call = custom-call:TopK"] == (3_000, 1)
    assert summary.op_seconds("TopK") == (pytest.approx(3e-6), 1)
    # the modules line is not an op line: it would make the chip look busy
    assert all("jit_batch_topk" not in name for name, _, _ in summary.ops)


def test_gaps_are_named_by_the_host_span_over_them(summary):
    gaps = {(start, ns): label for start, ns, label in summary.gaps}
    assert gaps == {
        (1_000, 1_000): tracereduce.NO_HOST_SPAN,
        (7_000, 2_000): "PjitFunction(batch_topk_scores_t)",
        (10_000, 1_000): tracereduce.NO_HOST_SPAN,
    }


def test_breakdown_has_at_most_ten_plain_entries(summary):
    b = tracereduce.breakdown(summary)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "fusion_fusion_f32_64_100_"
    assert b["device_ops"][0][1] == pytest.approx(5e-6)
    names = [n for n, _ in b["idle_gaps"]]
    assert names[0].startswith("2_gaps_during_no_jax_call")
    assert all(" " not in n and "/" not in n for n in names)


def test_a_trace_without_device_ops_is_an_error():
    from jax.profiler import ProfileData

    host_only = SYNTHETIC[SYNTHETIC.index('planes { id: 2'):]
    with pytest.raises(ValueError, match="no device operation"):
        tracereduce.reduce_planes(
            ProfileData.from_text_proto(host_only).planes
        )


def test_union_of_intervals():
    total, merged = tracereduce.union_ns([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert total == 6 and merged == [(0, 3), (5, 8)]


RECORDED = HERE / "recorded_v5e.xplane.pb"


def test_recorded_v5e_trace_reduces():
    """4 s of rec-yambda-r64.serve-saturated traced on one v5e chip (my chip
    run, PR 25): 71 scored batches, each a product and a TopK."""
    s = tracereduce.reduce_file(RECORDED)
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(4.0007, abs=1e-3)
    assert s.busy_s == pytest.approx(2.8166, abs=1e-3)
    assert s.idle_share == pytest.approx(0.296, abs=1e-3)
    topk_s, batches = s.op_seconds("TopK")
    assert batches == 71 and topk_s == pytest.approx(2.2933, abs=1e-3)
    assert sum(ns for _, ns, _ in s.ops) / 1e9 == pytest.approx(
        s.busy_s, rel=1e-3)    # one op at a time on this chip
    b = tracereduce.breakdown(s)
    assert b["device_ops"][0][0].startswith("custom-call_custom-call:TopK")
    assert b["device_ops"][1][0] == "fusion_fusion_f32_64_9390623_"
    assert b["idle_gaps"][0][0].startswith("73_gaps_during_no_jax_call")


def test_op_kind_sums_the_instances_of_one_operation():
    kind = tracereduce.op_kind
    assert kind('%fusion.12 = f32[64,9]{1,0:T(8,128)} fusion(f32[64,64]{1,0} '
                '%x), kind=kOutput') == "fusion fusion f32[64,9]"
    assert kind('%custom-call.3 = (f32[64,16]{1,0}, s32[64,16]{1,0}) '
                'custom-call(f32[64,9]{1,0} %f), custom_call_target="TopK"'
                ) == "custom-call custom-call:TopK (f32[64,16], s32[64,16])"
    assert kind("not an HLO line") == "not an HLO line"
