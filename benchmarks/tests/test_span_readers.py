"""The nine span readers, ``spans.py``, and the pure parts of the two by-hand
commands, with a made-up ``ctx`` and made-up planes: no chip, no run."""

from types import SimpleNamespace

import pytest

from benchmarks import manifest, recorder_cost, span_gaps, spans
from benchmarks.loops.closed import Record
from cometbft_tpu.libs import tracing

NEW = {
    "entry_sign_bytes_ms": 2.0,
    "entry_self_ms": 1.0,  # 20 - 2 - 17
    "seam_self_ms": 3.0,  # 17 - 14
    "sched_submit_ms": 4.0,
    "sched_wait_ms": 9.0,
    "sched_flush_self_ms": 1.5,  # 5 - 3.5
    "sched_resolve_ms": 0.5,
    "host_pack_ms": 2.5,
    "launch_ms": 0.75,
}
# stage -> duration in ms of the one span a request (or a flush) has
SPANS = {
    "verify.commit": 20.0, "commit.sign_bytes": 2.0, "batch.verify": 17.0,
    "sched.segment": 14.0, "sched.submit": 4.0, "sched.wait": 9.0,
    "sched.flush": 5.0, "sched.dispatch": 3.5, "sched.resolve": 0.5,
    "verify.pack": 2.5, "verify.launch": 0.75,
}


@pytest.fixture
def tracer():
    tracing.reset_tracer()
    yield tracing.get_tracer()
    tracing.reset_tracer()


def ctx_of(records):
    return SimpleNamespace(records=records)


def fill(tracer, first: float, requests: int, every: float = 0.1):
    records = []
    for k in range(requests):
        t = first + k * every
        for stage, ms in SPANS.items():
            tracer.record_span(stage, t, t + ms / 1e3)
        records.append(Record(k, t, t + 0.020, ("accepted",), 117))
    return records


def test_every_new_reader_reads_its_stage(tracer):
    records = fill(tracer, 1000.25, 100)
    ctx = ctx_of(records)
    for name, want in NEW.items():
        reader = manifest.reader("layers", name)
        assert (reader.NAME, reader.UNIT, reader.SOURCE) == (name, "ms", "program_span")
        assert reader.read(ctx) == pytest.approx(want), name
    # whole seconds inside [1000.25, 1010.17]: 1001..1009, ten requests each
    assert spans.counts(ctx) == (90, 90)


def test_the_manifest_lists_the_readers_with_their_layers():
    listed = {m["name"]: m for m in manifest.load()["per_layer"]}
    for name in NEW:
        reader = manifest.reader("layers", name)
        m = listed[name]
        assert (m["layer"], m["moves"], m["better"], m["source"], m["unit"]) == (
            reader.LAYER, reader.MOVES, reader.BETTER, reader.SOURCE, reader.UNIT)
        assert m["workloads"] == ["val10k-commit-stream", "val175-commit-stream"]


def test_a_mean_divides_by_the_stages_own_count(tracer):
    """Two flushes a request: the flush metrics are means a flush."""
    records = fill(tracer, 2000.0, 50)
    for r in records:
        tracer.record_span("sched.flush", r.start, r.start + 0.001)
        tracer.record_span("sched.resolve", r.start, r.start + 0.0015)
    ctx = ctx_of(records)
    assert manifest.reader("layers", "sched_resolve_ms").read(ctx) == pytest.approx(1.0)
    assert manifest.reader("layers", "sched_flush_self_ms").read(ctx) == pytest.approx(
        (5.0 + 1.0 - 3.5) / 2)
    assert spans.counts(ctx)[1] == 2 * spans.counts(ctx)[0]


def test_nothing_to_read_gives_none(tracer, monkeypatch):
    readers = [manifest.reader("layers", n) for n in NEW]
    assert all(r.read(ctx_of([])) is None for r in readers)
    # spans, but none in the window
    fill(tracer, 100.0, 5)
    late = [Record(0, 500.0, 505.0, ("accepted",), 1)]
    assert all(r.read(ctx_of(late)) is None for r in readers)
    # a stage that has no span: its reader alone is silent
    tracer.reset()
    records = fill(tracer, 3000.0, 30)
    ctx = ctx_of(records)
    ctx._stage_totals = {
        k: v for k, v in spans.totals(ctx).items() if k != "verify.launch"
    }
    assert manifest.reader("layers", "launch_ms").read(ctx) is None
    assert manifest.reader("layers", "host_pack_ms").read(ctx) is not None
    # the parent's recorder has no store: every reader is silent, none raises
    monkeypatch.delattr(tracing.Tracer, "stage_totals")
    assert all(r.read(ctx_of(records)) is None for r in readers)


def test_recorder_off_gives_none(tracer, monkeypatch):
    monkeypatch.setenv("COMETBFT_TPU_TRACE", "0")
    with tracing.span("verify.commit"):
        pass
    records = [Record(0, 0.0, 1e9, ("accepted",), 1)]
    assert manifest.reader("layers", "entry_self_ms").read(ctx_of(records)) is None


# -- span_gaps ---------------------------------------------------------------------


def test_gaps_are_cut_at_span_edges_and_keyed_by_the_span_opened_last():
    ms = 1_000_000
    requests = [("request", 0, 100 * ms), ("request", 110 * ms, 200 * ms)]
    device = [("k", 40 * ms, 60 * ms), ("k", 150 * ms, 170 * ms)]
    program = [
        ("verify.commit", 1 * ms, 99 * ms),
        ("sched.wait", 10 * ms, 95 * ms),       # the caller, blocked
        ("verify.pack", 12 * ms, 38 * ms),      # the dispatcher, opened later
        ("sched.resolve", 62 * ms, 90 * ms),
        ("verify.commit", 111 * ms, 199 * ms),  # second request: no inner span
    ]
    out = span_gaps.gaps_by_span(device, requests, program)
    assert out["window_s"] == pytest.approx(0.200)
    assert out["idle_s"] == pytest.approx(0.160)
    by = out["by_where"]
    before = by["inside a request, before its first device operation"]
    after = by["inside a request, after its last device operation"]
    # a gap is cut wherever a span opens or closes; each piece goes to the
    # span opened last among those open over it
    assert before == {
        "unnamed": pytest.approx(0.002),        # 0-1 and 110-111
        "verify.commit": pytest.approx(0.048),  # 1-10 and 111-150
        "sched.wait": pytest.approx(0.004),     # 10-12 and 38-40
        "verify.pack": pytest.approx(0.026),    # 12-38: the dispatcher's
    }
    assert after == {
        "sched.wait": pytest.approx(0.007),     # 60-62 and 90-95
        "sched.resolve": pytest.approx(0.028),  # 62-90
        "verify.commit": pytest.approx(0.033),  # 95-99 and 170-199
        "unnamed": pytest.approx(0.002),        # 99-100 and 199-200
    }
    assert by["between requests"] == {"unnamed": pytest.approx(0.010)}
    assert sum(sum(r.values()) for r in by.values()) == pytest.approx(out["idle_s"])


def test_gaps_sum_to_what_trace_reduce_calls_idle():
    from benchmarks import trace_reduce

    ms = 1_000_000
    requests = [("request", k * 50 * ms, (k * 50 + 45) * ms) for k in range(6)]
    device = [("op", (k * 50 + 10) * ms, (k * 50 + 17) * ms) for k in range(6)]
    program = [("sched.wait", (k * 50 + 2) * ms, (k * 50 + 40) * ms) for k in range(6)]
    planes = [
        ("/device:TPU:0", [("XLA Ops", [(n, a, b - a) for n, a, b in device])]),
        ("/host:CPU", [("t", [(n, a, b - a) for n, a, b in requests])]),
    ]
    trace = trace_reduce.reduce_planes(planes)
    out = span_gaps.gaps_by_span(device, requests, program)
    assert out["idle_s"] == pytest.approx(trace.window_s - trace.busy_s)
    for where, seconds in trace.idle_gaps.items():
        assert sum(out["by_where"][where].values()) == pytest.approx(seconds)


# -- recorder_cost -----------------------------------------------------------------


def test_by_tenth_means_a_stage_by_tenth_of_the_window():
    seconds = {
        100 + s: {"verify.commit": (10, 0.010 * (1 + s // 10))} for s in range(20)
    }
    out = recorder_cost.by_tenth(seconds, 100.0, 120.0)
    assert out["verify.commit"] == [1.0] * 5 + [2.0] * 5
    assert recorder_cost.by_tenth({5: {"x": (1, 1.0)}}, 100.0, 120.0) == {}
