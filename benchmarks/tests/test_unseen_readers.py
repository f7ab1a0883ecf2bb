"""The readers of PR 36 (the hand-offs, the watchdog's trips, the parts of the
launch and of the seam, what of a wait no span names, the host's pressure)
with a made-up store, and their constants against ``BENCHMARK.json``: no
chip, no run."""

from types import SimpleNamespace

import pytest

from benchmarks import manifest, spans
from benchmarks.loops.closed import Record
from cometbft_tpu.libs import tracing

ALL = [
    "val10k-commit-stream", "val175-commit-stream", "light1k-skipping-sync",
    "val175-receive-routine", "val10k-commit-stream-x4",
]
ONE_CHIP = ALL[:4]
# stage -> ms of the one span a request has; the wait's pieces cover the wait
WAIT = {
    "sched.queue": 0.25, "sched.flush": 2.0, "sched.handoff.fetch": 0.5,
    "sched.fetch": 5.0, "sched.landed": 0.125, "sched.resolve": 0.375,
    "sched.handoff.wake": 0.75,
}
SPANS = dict(WAIT, **{
    "sched.wait": sum(WAIT.values()),
    "verify.dispatch": 1.5, "verify.launch": 1.0,
    "verify.launch.lookup": 0.125, "verify.launch.put": 0.625,
    "verify.launch.call": 0.25,
    "verify.fetch": 4.5, "verify.fetch.pull": 4.0,
    "batch.add": 1.25, "batch.keys": 6.0, "batch.lookup": 0.5,
    "batch.writeback": 1.0,
})
# metric -> (what the made-up store reads, unit, layer, moves, cells)
MEANS = {
    "handoff_fetch_ms": (0.5, "scheduler", ALL),
    "handoff_wake_ms": (0.75, "scheduler", ALL),
    "sched_landed_ms": (0.125, "scheduler", ALL),
    "wait_unseen_ms": (0.0, "scheduler", ALL),
    "watchdog_ms": (1.0, "supervisor", ALL),  # (1.5 - 1.0) + (4.5 - 4.0)
    "launch_lookup_ms": (0.125, "executable", ALL),
    "launch_put_ms": (0.625, "executable", ONE_CHIP),
    "launch_call_ms": (0.25, "executable", ALL),
    "seam_add_ms": (1.25, "batch seam", ALL),
    "seam_keys_ms": (6.0, "batch seam", ALL),
    "seam_lookup_ms": (0.5, "batch seam", ALL),
    "seam_writeback_ms": (
        1.0, "batch seam", [c for c in ALL if c != "val175-receive-routine"]),
}
HOST = "host_cpu_ms"


@pytest.fixture
def tracer():
    tracing.reset_tracer()
    tr = tracing.get_tracer()
    tr.set_host_readers({})  # this machine's own pressure is no test's input
    yield tr
    tracing.reset_tracer()


def ctx_of(records):
    return SimpleNamespace(records=records)


def fill(tracer, first: float, requests: int, stages=SPANS, every: float = 0.1):
    records = []
    for k in range(requests):
        t = first + k * every
        for stage, ms in stages.items():
            tracer.record_span(stage, t, t + ms / 1e3)
        records.append(Record(k, t, t + 0.020, ("accepted",), 117))
    return records


def listed():
    return {m["name"]: m for m in manifest.load()["per_layer"]}


def test_every_reader_reads_its_stage(tracer):
    ctx = ctx_of(fill(tracer, 1000.25, 100))
    for name, (want, _, _) in MEANS.items():
        assert manifest.reader("layers", name).read(ctx) == pytest.approx(
            want, abs=1e-9), name


def test_the_manifest_lists_the_readers_with_their_layers():
    entries = listed()
    for name, (_, layer, cells) in MEANS.items():
        r, m = manifest.reader("layers", name), entries[name]
        assert (r.NAME, r.UNIT, r.BETTER, r.SOURCE, r.LAYER, r.MOVES) == (
            name, "ms", "lower", "program_span", layer, "verify_p50_ms")
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
            r.UNIT, r.BETTER, r.SOURCE, r.LAYER, r.MOVES)
        assert m["workloads"] == cells, name
    r, m = manifest.reader("layers", HOST), entries[HOST]
    assert (r.NAME, r.UNIT, r.BETTER, r.SOURCE, r.LAYER, r.MOVES) == (
        HOST, "ms", "lower", "program_counter", "host", "verify_p95_ms")
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        r.UNIT, r.BETTER, r.SOURCE, r.LAYER, r.MOVES)
    assert m["workloads"] == ALL
    # of the program's five host counters only the one the chip's host keeps
    # is a metric (PERF.md section 3): none that would read nothing there
    assert [n for n, e in entries.items() if e["layer"] == "host"] == [HOST]


def test_the_thirteen_stand_at_the_end_and_pr_34s_five_right_before_them():
    """A PR that changes the program may only append to ``per_layer`` (the
    driver reads an insertion as an edit of the entry after it), so
    ``test_mesh_cell_cpu.py:56``'s pin of the LAST five fails until a
    ``benchmark`` PR turns it into this one; the rest of what it pins holds."""
    names = list(listed())
    assert names[-13:] == list(MEANS) + [HOST]
    assert names[-18:-13] == [
        "shards_per_dispatch", "mesh_put_ms", "mesh_kernel_us_per_sig",
        "ed25519_verify_mesh_roofline", "mesh_collective_us"]
    assert len(names) == len(set(names)) == 48


def test_unseen_is_zero_where_the_pieces_cover_the_wait_and_a_planted_gap_where_not(
    tracer,
):
    read = manifest.reader("layers", "wait_unseen_ms").read
    assert read(ctx_of(fill(tracer, 2000.0, 50))) == pytest.approx(0.0, abs=1e-9)
    tracer.reset()
    gapped = dict(SPANS, **{"sched.wait": SPANS["sched.wait"] + 0.85})
    assert read(ctx_of(fill(tracer, 3000.0, 50, gapped))) == pytest.approx(0.85)
    # a wait whose flush served a cache of its own kind (no fetch at all):
    # the missing pieces count as nothing, the wait is all unseen but the wake
    tracer.reset()
    bare = {"sched.wait": 1.0, "sched.handoff.wake": 0.25}
    assert read(ctx_of(fill(tracer, 4000.0, 50, bare))) == pytest.approx(0.75)


def test_the_watchdogs_trips_on_one_chip_and_on_a_mesh(tracer):
    read = manifest.reader("layers", "watchdog_ms").read
    mesh = {k: v for k, v in SPANS.items() if k != "verify.fetch.pull"}
    records = fill(tracer, 5000.0, 40, mesh)
    for r in records:  # four shards a dispatch, each holding its own pull
        for _ in range(4):
            tracer.record_span("mesh.shard", r.start, r.start + 1.0 / 1e3)
    # (1.5 - 1.0) + (4.5 - 4 x 1.0)
    assert read(ctx_of(records)) == pytest.approx(1.0)
    # two dispatches a request (the light client): a mean a DISPATCH
    tracer.reset()
    records = fill(tracer, 6000.0, 40)
    fill(tracer, 6000.0, 40)
    assert read(ctx_of(records)) == pytest.approx(1.0)


def test_the_hosts_cpu_is_a_request_of_the_cell_whatever_its_spans(tracer):
    """The pseudo-stage holds ``[1, difference]`` a sampled second; the reader
    turns it into CPU a request with the cell's OWN records that ended in the
    same whole seconds, not with a span's count (two spans a request here)."""
    t = [7000.5]
    tracer.set_clock(lambda: t[0])
    cpu = [0.0]
    tracer.set_host_readers({"host.cpu": lambda tids: cpu[0]})
    records = []
    for k in range(12 * 8):  # eight requests a second for twelve seconds
        t[0] = 7000.5 + k / 8.0
        cpu[0] = 0.4 * (t[0] - 7000.5)  # two fifths of a core, all along
        for _ in range(2):
            with tracer.span("verify.commit"):
                pass
        records.append(Record(k, t[0] - 0.01, t[0], ("accepted",), 1))
    tracer.set_clock(None)
    ctx = ctx_of(records)
    # 400 ms of CPU a second over eight requests a second
    assert manifest.reader("layers", HOST).read(ctx) == pytest.approx(50.0, rel=0.02)
    assert spans.totals(ctx)["host.cpu"][0] >= 9  # the whole seconds inside
    # half the requests in the same seconds: twice the CPU a request
    assert manifest.reader("layers", HOST).read(
        SimpleNamespace(records=records[::2], _stage_totals=ctx._stage_totals)
    ) == pytest.approx(100.0, rel=0.05)


def test_nothing_to_read_gives_none(tracer, monkeypatch):
    names = list(MEANS) + [HOST]
    readers = [manifest.reader("layers", n) for n in names]
    assert all(r.read(ctx_of([])) is None for r in readers)
    # the parent's stages only (no hand-off, no lap, no host counter): every
    # new reader is silent, none raises
    parent = {
        "verify.commit": 20.0, "batch.verify": 17.0, "sched.segment": 14.0,
        "sched.submit": 4.0, "sched.wait": 9.0, "sched.flush": 5.0,
        "sched.dispatch": 3.5, "sched.fetch": 5.0, "sched.resolve": 0.5,
        "verify.pack": 2.5, "verify.dispatch": 1.5, "verify.launch": 0.75,
        "verify.fetch": 4.5, "mesh.put": 0.5,
    }
    ctx = ctx_of(fill(tracer, 8000.0, 30, parent))
    assert all(r.read(ctx) is None for r in readers)
    # a host that keeps other counters and not this one: the reader is silent
    tracer.reset()
    ctx = ctx_of(fill(tracer, 9000.0, 30))
    ctx._stage_totals = dict(spans.totals(ctx), **{"host.switches": (2, 80.0)})
    assert manifest.reader("layers", HOST).read(ctx) is None
    # 0.25 s of CPU a second, ten requests a second (``fill``'s spacing)
    ctx._stage_totals["host.cpu"] = (2, 0.5)
    assert manifest.reader("layers", HOST).read(ctx) == pytest.approx(25.0, rel=0.06)
    # a parent without the store, and a recorder switched off
    monkeypatch.delattr(tracing.Tracer, "stage_totals")
    assert all(r.read(ctx_of(fill(tracer, 9500.0, 3))) is None for r in readers)
