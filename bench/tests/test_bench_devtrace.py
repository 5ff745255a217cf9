"""The trace reduction on a small recorded trace: two gateway steps of
granite_3_2b.chat on one TPU v5e (bench/tests/recorded_trace.json.gz, op
names cut to their HLO names, every device event on device 0), checked
against plain arithmetic."""
import gzip
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import counts, devtrace, peaks, spec  # noqa: E402

TRACE = pathlib.Path(__file__).with_name("recorded_trace.json.gz")


@pytest.fixture(scope="module")
def tr():
    return json.loads(gzip.open(TRACE, "rt").read())


def test_busy_time_against_a_microsecond_timeline(tr):
    t0, t1 = devtrace.window(tr)
    line = np.zeros(int((t1 - t0) // 1000) + 2, bool)
    for _, s, d, _ in tr["device_ops"]:
        a = int((max(s, t0) - t0) // 1000)
        b = int(np.ceil((min(s + d, t1) - t0) / 1000))
        line[a:b] = True
    busy = devtrace.busy_s(tr)
    assert 0 < busy < (t1 - t0) / 1e9
    assert abs(line.sum() / 1e6 - busy) < 0.01 * busy + 2e-4


def test_gaps_and_busy_fill_the_window(tr):
    t0, t1 = devtrace.window(tr)
    gaps = devtrace.idle_gaps(tr, n=10**6)
    assert sum(g for _, g in gaps) + devtrace.busy_s(tr) == \
        pytest.approx((t1 - t0) / 1e9, rel=1e-9)
    assert [g for _, g in gaps] == sorted((g for _, g in gaps), reverse=True)
    assert {lab for lab, _ in gaps} <= {"bench.step", "bench.submit", "none"}


def test_kernel_module_and_op_times(tr):
    kernel = sum(d for n, _, d, _ in tr["device_ops"]
                 if n.startswith("%paged_attention.")) / 1e9
    assert kernel > 0
    assert devtrace.op_s(tr, "paged_attention") == pytest.approx(kernel)
    fused = sum(d for n, _, d, _ in tr["device_modules"]
                if n.startswith("jit_fused(")) / 1e9
    assert devtrace.module_s(tr, "jit_fused") == pytest.approx(fused)
    top = devtrace.top_ops(tr)
    assert len(top) == 10 and "while" not in [k for k, _ in top]
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)
    assert devtrace.op_kind("%broadcast.5189.clone.2 = bf16[2]") == \
        "broadcast"


def _run(tr, **counts_kw):
    class Run:
        pass
    r = Run()
    r.trace = tr
    r.model = json.loads((ROOT / "bench/configs/granite_3_2b.json")
                         .read_text())["model"]
    r.peaks = peaks.peaks_for("TPU v5 lite")
    r.counts = counts
    r.trace_steps = [1] * len(devtrace.spans(tr, "bench.step"))
    r.trace_counts = counts_kw
    return r


def test_readers_on_the_recorded_trace(tr):
    keys = [np.arange(201, 203), np.arange(90, 92)]
    r = _run(tr, c0={"steps": 10}, c1={"steps": 13}, decode_keys=keys,
             prompt_lens=[150])
    fused = devtrace.module_s(tr, "jit_fused")
    assert spec.reader(ROOT, "scan_step_ms.chat")(r) == \
        pytest.approx(1e3 * fused / 3)
    flops = counts.decode_flops(r.model, [201, 202, 90, 91]) + \
        counts.prefill_flops(r.model, np.arange(150), 1)
    assert spec.reader(ROOT, "step_mfu.chat")(r) == \
        pytest.approx(100 * flops / (fused * 197e12))
    kernel = devtrace.op_s(tr, "paged_attention")
    least = counts.paged_attn_bytes(r.model, [201, 202, 90, 91]) / 819e9
    assert spec.reader(ROOT, "paged_attention_roofline.chat")(r) == \
        pytest.approx(100 * least / kernel)
    idle = spec.reader(ROOT, "inflight_idle_share.chat")(r)
    assert 0 <= idle < 100


# each reader's value on the recorded trace, as the one-device code gave it
# before the readers were defined per device: the same to the last digit
RECORDED = [
    ("scan_step_ms.chat", 64.44685666666666),
    ("step_mfu.chat", 1.9800926135594594),
    ("paged_attention_roofline.chat", 0.2523182788705645),
    ("inflight_idle_share.chat", 12.150160163167422),
    ("scan_step_ms.batch", 64.44685666666666),
    ("step_mfu.batch", 1.9800926135594594),
    ("paged_attention_roofline.batch", 0.2523182788705645),
    ("inflight_idle_share.batch", 12.150160163167422),
]


@pytest.mark.parametrize("metric,value", RECORDED)
def test_readers_keep_their_one_device_values(tr, metric, value):
    r = _run(tr, c0={"steps": 10}, c1={"steps": 13},
             decode_keys=[np.arange(201, 203), np.arange(90, 92)],
             prompt_lens=[150])
    assert spec.reader(ROOT, metric)(r) == value


def test_trace_numbers_keep_their_one_device_values(tr):
    assert devtrace.busy_s(tr) == 0.19333192
    assert devtrace.module_s(tr, "jit_fused") == 0.19334057
    assert devtrace.op_s(tr, "paged_attention") == 0.023785297
    assert devtrace.top_ops(tr) == [
        ["copy", 0.026238501], ["fusion", 0.025539385],
        ["bitcast_dynamic-update-slice_fusion", 0.025318706],
        ["paged_attention", 0.023785297],
        ["dynamic-slice_bitcast_fusion", 0.022722519],
        ["bitcast_add_fusion", 0.021981309], ["broadcast", 0.020518505],
        ["sort", 0.012629409], ["constant_dynamic-slice_fusion", 0.00747181],
        ["constant_dynamic-update-slice_fusion", 0.002658443]]
    assert devtrace.idle_gaps(tr) == [
        ["none", 0.009344742], ["bench.step", 0.0040725],
        ["bench.step", 0.003353559], ["bench.step", 0.003347971],
        ["bench.step", 0.002953367], ["bench.step", 0.001352194],
        ["bench.step", 0.00122463], ["bench.step", 0.000692992],
        ["bench.step", 0.000668249], ["bench.step", 0.000591024]]


def test_readers_with_nothing_to_read_return_none(tr):
    r = _run(tr, c0={"steps": 10}, c1={"steps": 10})
    r.trace = None
    for name in ("scan_step_ms.chat", "step_mfu.chat",
                 "paged_attention_roofline.chat",
                 "inflight_idle_share.chat"):
        assert spec.reader(ROOT, name)(r) is None
