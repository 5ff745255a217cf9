"""The trace reduction on a trace of two devices, written as the profiler
writes it: ``normalize`` keeps each device op's device and the program's
``sprout.*`` host spans beside the benchmark's, and each reader's number
is its per-device definition (on one device, the one-device formula)."""
import pathlib
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import devtrace, peaks, spec  # noqa: E402

MS = 1_000_000          # ns
# [name, start_ms, duration_ms] per line; the two devices run the same two
# programs for different times
DEVICE = {
    0: {"XLA Ops": [["%fusion.1 = bf16[8]{0} fusion()", 10, 20],
                    ["%paged_attention.2 = bf16[8]{0} custom-call()", 30, 10],
                    ["%fusion.3 = bf16[8]{0} fusion()", 60, 20]],
        "XLA Modules": [["jit_fused(1)", 10, 30], ["jit_fused(1)", 60, 20]]},
    1: {"XLA Ops": [["%fusion.1 = bf16[8]{0} fusion()", 12, 18],
                    ["%paged_attention.2 = bf16[8]{0} custom-call()", 30, 6],
                    ["%fusion.3 = bf16[8]{0} fusion()", 62, 28]],
        "XLA Modules": [["jit_fused(1)", 12, 24], ["jit_fused(1)", 62, 28]]},
}
HOST = [["bench.window", 0, 100], ["bench.step", 10, 40],
        ["bench.step", 60, 30], ["sprout.engine.prepare", 40, 20],
        ["somebody.else", 0, 100]]


def _plane(pid, name, lines):
    names = sorted({e[0] for evs in lines.values() for e in evs})
    body = "".join(
        f'  lines {{\n    id: {i + 1}\n    name: "{line}"\n'
        + "".join(f"    events {{ metadata_id: {names.index(n) + 1} "
                  f"offset_ps: {s * MS * 1000} "
                  f"duration_ps: {d * MS * 1000} }}\n" for n, s, d in evs)
        + "  }\n" for i, (line, evs) in enumerate(lines.items()))
    meta = "".join(f'  event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                   f'name: "{n}" }} }}\n' for i, n in enumerate(names))
    return f'planes {{\n  id: {pid}\n  name: "{name}"\n{body}{meta}}}\n'


@pytest.fixture(scope="module")
def tr(tmp_path_factory):
    from jax.profiler import ProfileData
    text = _plane(1, "/host:CPU", {"python": HOST}) + "".join(
        _plane(2 + dev, f"/device:TPU:{dev}", lines)
        for dev, lines in DEVICE.items())
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "rec"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return devtrace.normalize(str(d.parents[2]))


def test_normalize_keeps_devices_and_program_spans(tr):
    assert devtrace.devices(tr) == [0, 1]
    assert sorted(e[3] for e in tr["device_ops"]) == [0, 0, 0, 1, 1, 1]
    assert sorted({e[0] for e in tr["host_spans"]}) == \
        ["bench.step", "bench.window", "sprout.engine.prepare"]
    assert all(len(e) == 3 for e in tr["host_spans"])


def test_device_times_are_per_device_means(tr):
    # busy: device 0 [10, 40] and [60, 80], device 1 [12, 36] and [62, 90]
    assert devtrace.busy_s(tr) == pytest.approx((50 + 52) / 2 / 1e3)
    assert devtrace.module_s(tr, "jit_fused") == \
        pytest.approx((50 + 52) / 2 / 1e3)
    assert devtrace.op_s(tr, "paged_attention") == \
        pytest.approx((10 + 6) / 2 / 1e3)
    assert devtrace.top_ops(tr) == [
        ["fusion", pytest.approx((40 + 46) / 2 / 1e3)],
        ["paged_attention", pytest.approx(8 / 1e3)]]


def test_idle_gaps_are_where_no_device_runs(tr):
    # any device busy: [10, 40] and [60, 90]; the gap at 40 to 60 is the
    # program's prepare span, the others lie outside every step
    assert devtrace.idle_gaps(tr) == [
        ["sprout.engine.prepare", pytest.approx(0.02)],
        ["none", pytest.approx(0.01)], ["none", pytest.approx(0.01)]]


def _run(tr):
    stub = types.SimpleNamespace(
        decode_flops=lambda m, keys: 1e12,
        prefill_flops=lambda m, pos, n: 0.0,
        paged_attn_bytes=lambda m, keys: 4.095e9,
        paged_attn_flops=lambda m, keys: 1e9)
    return types.SimpleNamespace(
        trace=tr, model={}, peaks=peaks.peaks_for("TPU v5 lite"),
        counts=stub, trace_steps=[3, 4],
        trace_counts={"c0": {"steps": 10}, "c1": {"steps": 15},
                      "decode_keys": [np.arange(4)], "prompt_lens": []})


@pytest.mark.parametrize("metric,value", [
    # the fused programs' mean device time, 51 ms, over 5 scan steps
    ("scan_step_ms.chat", 51 / 5),
    # FLOPs over (2 chips x 51 ms x 197 TF/s)
    ("step_mfu.batch", 100 * 1e12 / (2 * 0.051 * 197e12)),
    # 4.095 GB over (2 chips x 819 GB/s) = 2.5 ms, over the kernel's 8 ms
    ("paged_attention_roofline.batch", 100 * 2.5 / 8),
    # steps span 70 ms; device 0 runs 50 ms of them, device 1 52 ms
    ("inflight_idle_share.batch", ((1 - 50 / 70) + (1 - 52 / 70)) / 2 * 100),
])
def test_readers_per_device(tr, metric, value):
    assert spec.reader(ROOT, metric)(_run(tr)) == pytest.approx(value)


def test_one_device_alone_reads_the_one_device_formulas(tr):
    one = {"device_ops": [e for e in tr["device_ops"] if e[3] == 1],
           "device_modules": [e for e in tr["device_modules"] if e[3] == 1],
           "host_spans": tr["host_spans"]}
    r = _run(one)
    assert spec.reader(ROOT, "scan_step_ms.chat")(r) == \
        pytest.approx(52 / 5)
    assert spec.reader(ROOT, "step_mfu.chat")(r) == \
        pytest.approx(100 * 1e12 / (0.052 * 197e12))
    assert spec.reader(ROOT, "paged_attention_roofline.chat")(r) == \
        pytest.approx(100 * 5 / 6)
    assert spec.reader(ROOT, "inflight_idle_share.chat")(r) == \
        pytest.approx(100 * (1 - 52 / 70))
    assert devtrace.busy_s(one) == pytest.approx(0.052)
