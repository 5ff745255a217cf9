"""The readers of the program's own spans and marks, on profiler files
written from recorded events: a traced tiny.chat run on the CPU
(bench/tests/recorded_spans.json.gz) and a hand-made trace; and ``None``
where the trace holds no ``sprout.*`` event (a program without them) or
the run has no trace."""
import gzip
import json
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import progtrace, spec  # noqa: E402

RECORDED = pathlib.Path(__file__).with_name("recorded_spans.json.gz")
METRICS = ("queue_wait_ms.chat", "prefill_ms.chat",
           "host_ms_per_dispatch.chat", "host_ms_per_dispatch.batch")
MS = 1_000_000


def _value(v) -> str:
    if isinstance(v, str):
        return f"str_value: {json.dumps(v)}"
    if isinstance(v, int):
        return f"int64_value: {v}"
    return f"double_value: {v!r}"


def write_trace(root: pathlib.Path, cell: str, events) -> None:
    """A one-plane XSpace holding ``[name, start_ns, duration_ns, stats]``
    events on one host line, as the profiler writes it."""
    from jax.profiler import ProfileData
    names = sorted({e[0] for e in events})
    keys = sorted({k for e in events for k in e[3]})
    ev = "".join(
        f"    events {{ metadata_id: {names.index(n) + 1} "
        f"offset_ps: {s * 1000} duration_ps: {d * 1000} "
        + "".join(f"stats {{ metadata_id: {keys.index(k) + 1} "
                  f"{_value(v)} }} " for k, v in st.items())
        + "}\n" for n, s, d, st in events)
    meta = "".join(f'  event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                   f'name: "{n}" }} }}\n' for i, n in enumerate(names))
    meta += "".join(f'  stat_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                    f'name: "{k}" }} }}\n' for i, k in enumerate(keys))
    text = ('planes {\n  id: 1\n  name: "/host:CPU"\n'
            f'  lines {{\n    id: 1\n    name: "python"\n{ev}  }}\n{meta}}}\n')
    d = root / "bench" / "traces" / cell / "plugins" / "profile" / "rec"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))


def _run(root, cell="granite_3_2b.chat", trace=True):
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(root=root, name=cell),
        trace={} if trace else None)


def _read(metric, run):
    return spec.reader(ROOT, metric)(run)


@pytest.fixture(scope="module")
def recorded():
    return json.loads(gzip.open(RECORDED, "rt").read())["events"]


@pytest.mark.parametrize("metric,value", [
    ("queue_wait_ms.chat", 1.9911365008738358),
    ("prefill_ms.chat", 24.378954101121053),
    ("host_ms_per_dispatch.chat", 7.269356152173913),
])
def test_readers_on_the_recorded_cpu_trace(tmp_path, recorded, metric,
                                           value):
    write_trace(tmp_path, "granite_3_2b.chat", recorded)
    assert _read(metric, _run(tmp_path)) == pytest.approx(value, rel=1e-12)


HANDMADE = [
    ["bench.window", 0, 100 * MS, {}],
    # a step that dispatched: 20 ms, 10 of them waiting in the fetch
    ["sprout.gateway.step", 10 * MS, 20 * MS, {}],
    ["sprout.engine.dispatch", 12 * MS, 1 * MS, {"bs": 8, "k": 8,
                                                "mode": "greedy", "chunk": 0,
                                                "cold": 0}],
    ["sprout.engine.fetch", 15 * MS, 10 * MS, {}],
    ["sprout.request.first_token", 26 * MS, 0,
     {"rid": 1, "wait_us": 1000.0, "prefill_us": 10000.0}],
    # a step that did not (an idle engine): left out
    ["sprout.gateway.step", 40 * MS, 5 * MS, {}],
    # another that did: 30 ms, 5 of them in the fetch
    ["sprout.gateway.step", 50 * MS, 30 * MS, {}],
    ["sprout.engine.dispatch", 52 * MS, 1 * MS, {"bs": 8, "k": 4,
                                                "mode": "full", "chunk": 128,
                                                "cold": 1}],
    ["sprout.engine.fetch", 55 * MS, 5 * MS, {}],
    ["sprout.request.first_token", 61 * MS, 0,
     {"rid": 2, "wait_us": 3000.0, "prefill_us": 20000.0}],
    # after the window closed: left out
    ["sprout.gateway.step", 120 * MS, 30 * MS, {}],
    ["sprout.engine.dispatch", 121 * MS, 1 * MS, {"bs": 1, "k": 1,
                                                 "mode": "greedy",
                                                 "chunk": 0, "cold": 0}],
    ["sprout.request.first_token", 125 * MS, 0,
     {"rid": 3, "wait_us": 9e9, "prefill_us": 9e9}],
]


@pytest.mark.parametrize("metric,value", [
    ("queue_wait_ms.chat", (1.0 + 3.0) / 2),
    ("prefill_ms.chat", (10.0 + 20.0) / 2),
    ("host_ms_per_dispatch.chat", ((20 - 10) + (30 - 5)) / 2),
    ("host_ms_per_dispatch.batch", ((20 - 10) + (30 - 5)) / 2),
])
def test_readers_on_a_handmade_trace(tmp_path, metric, value):
    cell = "minicpm_2b.batch" if metric.endswith(".batch") \
        else "granite_3_2b.chat"
    write_trace(tmp_path, cell, HANDMADE)
    assert _read(metric, _run(tmp_path, cell)) == pytest.approx(value)


def test_events_are_clipped_to_the_window_and_keep_their_stats(tmp_path):
    write_trace(tmp_path, "granite_3_2b.chat", HANDMADE)
    evs = progtrace.events(_run(tmp_path))
    assert [e[1] for e in evs] == sorted(e[1] for e in evs)
    assert all(e[1] + e[2] <= 100 * MS for e in evs)
    assert len(evs) == len(HANDMADE) - 4
    (cold,) = [e for e in progtrace.named(evs, "sprout.engine.dispatch")
               if e[3]["cold"]]
    assert cold[3] == {"bs": 8, "k": 4, "mode": "full", "chunk": 128,
                       "cold": 1}


@pytest.mark.parametrize("metric", METRICS)
def test_none_on_a_trace_without_program_spans(tmp_path, recorded, metric):
    cell = "minicpm_2b.batch" if metric.endswith(".batch") \
        else "granite_3_2b.chat"
    write_trace(tmp_path, cell,
                [e for e in recorded if e[0].startswith("bench.")])
    assert _read(metric, _run(tmp_path, cell)) is None


@pytest.mark.parametrize("metric", METRICS)
def test_none_without_a_trace(tmp_path, metric):
    assert _read(metric, _run(tmp_path, trace=False)) is None
    assert _read(metric, _run(tmp_path)) is None      # nothing written
