"""One run of one cell: set-up, warm-up, the window, the counters, the plain
reference, the result line.  ``run.py`` looks for the chip and then calls
``run_cell``; the tests call it without a chip.

Order of a run (and why):

 1. start the signing pool (spawned workers that hold the host library only)
    and start signing the cell's traffic from the seed;
 2. meanwhile import jax and the program; collect the chain; build the
    program's objects BEFORE the batch backend is resolved (once it is
    trusted, ``ValidatorSet.hash`` goes to the device's SHA-256 plane);
 3. resolve the backend and warm the executables the traffic can reach; send
    the warm-up heights (its own, never the pool's) through the timed call;
 4. the window, by the loop the traffic file names; with ``--trace 1`` the
    profiler is open over its first ``TRACE_SLICE_S`` seconds;
 5. read the counters and the device's memory peak, stop the program's
    threads, then run the plain reference over a seeded sample of the
    requests and compare; print.
"""

from __future__ import annotations

import gc
import json
import os
import random
import shutil
import statistics
import sys
import time
from types import SimpleNamespace

from benchmarks import chain as chainlib
from benchmarks import manifest, program, roofline, trace_reduce

TRACE_SLICE_S = 8.0
OUT_DIR = os.path.join(manifest.ROOT, ".bench_out")


def say(**rec) -> None:
    """A line for the reader, on standard error."""
    print(json.dumps(rec, sort_keys=True, default=str), file=sys.stderr, flush=True)


# -- judging -----------------------------------------------------------------------


def pick_sample(cell, chain, by_key, records, seed: int) -> list:
    """The requests the plain reference is run over: every rejected or
    unexpected verdict first (they are few and say most), then accepted
    ones drawn from the seed, up to the traffic file's budget of
    signatures, and at least ``reference_min_accepted`` of those."""
    budget = int(cell.traffic.get("reference_signatures", 40000))
    least = int(cell.traffic.get("reference_min_accepted", 2))
    rng = random.Random(f"tpu-bft-bench/{seed}/sample")
    plain, other = [], []
    for r in records:
        as_expected = r.verdict == cell.entry.expected(chain, by_key[r.key])
        is_plain = as_expected and r.verdict[0] == "accepted"
        (plain if is_plain else other).append(r)
    rng.shuffle(plain)
    rng.shuffle(other)
    sample, spent = [], 0
    for r in other:
        if spent + r.signatures > budget and sample:
            break
        sample.append(r)
        spent += r.signatures
    for k, r in enumerate(plain):
        if spent + r.signatures > budget and k >= least:
            break
        sample.append(r)
        spent += r.signatures
    return sample


def judge(cell, chain, records, pool, seed: int, set_up_wrong=None) -> dict:
    """Compare what the timed calls returned with the plain reference (on the
    sample) and with what the generator did to each height (on all).
    ``set_up_wrong`` names what set-up compared (warm-up verdicts, known
    answers) with the list of what it found wrong.  Every comparison is
    exact: each number's limit is 0."""
    entry = cell.entry
    by_key = {q.key: q for q in entry.requests(chain)}
    unexpected = [
        r for r in records if r.verdict != entry.expected(chain, by_key[r.key])
    ]
    sample = pick_sample(cell, chain, by_key, records, seed)
    items, spans = [], []
    for r in sample:
        got = entry.reference_items(chain, by_key[r.key])
        spans.append((len(items), len(items) + len(got)))
        items.extend(got)
    bits = pool.verify(items)
    wrong, disagrees = [], []
    for r, (a, b) in zip(sample, spans):
        req = by_key[r.key]
        want = entry.reference_verdict(chain, req, bits[a:b])
        if r.verdict != want:
            wrong.append((r.key, r.verdict, want))
        if want != entry.expected(chain, req):
            disagrees.append((r.key, want, entry.expected(chain, req)))
    keys = [r.key for r in records]
    compared = {
        "sample_verdicts_wrong": {"value": len(wrong), "limit": 0},
        "window_verdicts_unexpected": {"value": len(unexpected), "limit": 0},
        "reference_against_generator": {"value": len(disagrees), "limit": 0},
        "requests_sent_twice": {"value": len(keys) - len(set(keys)), "limit": 0},
    }
    for name, found in (set_up_wrong or {}).items():
        compared[name] = {"value": len(found), "limit": 0}
    return {
        "compared": compared,
        "correct": all(c["value"] <= c["limit"] for c in compared.values()),
        "failed": len(unexpected),
        "sampled_requests": len(sample),
        "sampled_rejections": sum(1 for r in sample if r.verdict != ("accepted",)),
        "sampled_signatures": len(items),
        "first_wrong": (wrong + disagrees)[:3],
        "first_unexpected": [
            (r.key, r.verdict) for r in unexpected[:3]
        ],
    }


# -- the run -----------------------------------------------------------------------


def say_window(window, asked_s: float, pool_left: int, counted: dict) -> None:
    """The window for the reader: how much of it was used, what a rejection
    cost beside an acceptance, whether the run slowed as it went (the median
    of each quarter), and every counter that moved."""
    records = window.records
    window_s = window.end - window.start
    by_verdict = {}
    for r in records:
        by_verdict.setdefault(r.verdict[0], []).append(r.end - r.start)
    quarters = [
        records[k * len(records) // 4:(k + 1) * len(records) // 4] for k in range(4)
    ]
    say(phase="window", requests=len(records), window_s=round(window_s, 3),
        asked_s=asked_s, share_of_window_used=round(window_s / asked_s, 3),
        pool_drained=window.drained, pool_left=pool_left,
        median_ms_by_verdict={
            k: [len(v), round(1e3 * statistics.median(v), 3)]
            for k, v in by_verdict.items()
        },
        counters={k: v for k, v in counted.items() if v},
        median_ms_by_quarter=[
            round(1e3 * statistics.median(r.end - r.start for r in part), 3)
            for part in quarters if part
        ])


def memory_peak_bytes() -> int:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class _Profiler:
    """Opens the profiler before the window's first request and closes it at
    the first request boundary ``TRACE_SLICE_S`` later."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.state = "idle"
        self.opened_at = None

    def between(self, now: float) -> None:
        import jax

        if self.state == "idle":
            shutil.rmtree(self.log_dir, ignore_errors=True)
            os.makedirs(self.log_dir, exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(self.log_dir, profiler_options=options)
            self.state = "open"
            self.opened_at = time.perf_counter()
        elif self.state == "open" and now - self.opened_at >= TRACE_SLICE_S:
            self.close()

    def close(self) -> None:
        import jax

        if self.state == "open":
            self.closed_at = time.perf_counter()
            jax.profiler.stop_trace()
            self.state = "closed"

    def annotate(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)


def start_signing(cell, seed: int, pool):
    """Step 1 of a run; ``run.py`` calls it before it imports jax, so that
    the workers sign while the chip is found."""
    return chainlib.submit(pool, cell.config, cell.traffic, cell.config_name, seed)


def run_cell(cell, seed: int, seconds: float, trace: bool, started: float,
             device: dict, pool=None, signing=None,
             require_backend: "str | None" = None) -> dict:
    """Everything of a run but the look for a chip.  ``started`` is the
    process's start on ``time.perf_counter``; ``device`` is what JAX
    reports; ``signing`` is what ``start_signing`` gave, where the caller
    started it.  Returns the result (the last line, as a dict)."""
    own_pool = pool is None
    pool = pool or chainlib.SignPool()
    try:
        signing = signing or start_signing(cell, seed, pool)
        return _run(cell, seed, seconds, trace, started, device, pool,
                    signing, require_backend)
    finally:
        program.shut_down()
        if own_pool:
            pool.close()


def _run(cell, seed, seconds, trace, started, device, pool, handle,
         require_backend):
    entry = cell.entry
    t = time.perf_counter
    t0 = t()
    if program.device_backend_is_resolved():
        raise RuntimeError("the batch backend was resolved before set-up")
    gc.disable()  # millions of small objects: no full collections meanwhile
    chain = chainlib.collect(handle, cell.config, cell.traffic, seed)
    chainlib.spot_check(chain)
    t_signed = t()
    state = entry.State(chain)
    requests = entry.requests(chain)
    t_built = t()
    warmed = entry.warm(chain)
    if require_backend and warmed["backend"] != require_backend:
        raise RuntimeError(
            f"the batch backend is {warmed['backend']!r}, not "
            f"{require_backend!r}: the device failed the program's self-check"
        )
    t_warmed = t()
    warm_wrong = [
        (q.key, got, entry.expected(chain, q))
        for q in entry.warmup_requests(chain)
        for got in [entry.call(state, q)]
        if got != entry.expected(chain, q)
    ]
    vectors_wrong = entry.known_answers() if hasattr(entry, "known_answers") else []
    # the pool's objects live to the end of the run: keep the collector off
    # them, so that no full collection falls into the window
    gc.collect()
    gc.freeze()
    gc.enable()
    before = program.counters()
    setup_s = t() - started
    say(phase="setup", workload=cell.name, seed=seed, device=device,
        validators=len(chain.pubs), pool_requests=len(requests),
        before_s=round(t0 - started, 2), signed_s=round(t_signed - t0, 2),
        built_s=round(t_built - t_signed, 2), warm_s=round(t_warmed - t_built, 2),
        warmup_requests_s=round(t() - t_warmed, 2), setup_s=round(setup_s, 2),
        warmed=warmed, warmup_wrong=warm_wrong[:3],
        known_answers_wrong=vectors_wrong, workers=pool.workers)

    profiler = _Profiler(os.path.join(OUT_DIR, "trace", cell.name)) if trace else None
    window = cell.loop.run(
        entry, state, chain, requests, seconds, cell.traffic,
        between=profiler.between if profiler else None,
        annotate=profiler.annotate if profiler else None,
    )
    counted = program.delta(before, program.counters())
    traced = None
    if profiler:
        profiler.close()
        traced = trace_reduce.reduce_file(trace_reduce.find_xplane(profiler.log_dir))
        shutil.rmtree(profiler.log_dir, ignore_errors=True)
    peak_bytes = memory_peak_bytes() if device.get("platform") != "cpu" else 0
    program.shut_down()
    state.requests.clear()

    records = window.records
    window_s = window.end - window.start
    say_window(window, seconds, len(requests) - len(records), counted)
    t_ref = t()
    verdict = judge(cell, chain, records, pool, seed, {
        "warmup_verdicts_wrong": warm_wrong,
        "known_answers_wrong": vectors_wrong,
    })
    say(phase="reference", seconds=round(t() - t_ref, 2),
        **{k: v for k, v in verdict.items() if k != "compared"})

    traced_records = []
    if profiler and profiler.state == "closed":
        traced_records = [
            r for r in records
            if r.start >= profiler.opened_at and r.end <= profiler.closed_at
        ]
    ctx = SimpleNamespace(
        records=records, window_s=window_s, setup_s=setup_s,
        counters=counted, trace=traced,
        traced_records=traced_records, chain=chain, config=cell.config,
        tier=warmed.get("tier"), chips=cell.chips,
        peaks=roofline.peaks(device["kind"]) if traced else None,
    )
    wanted = cell.per_layer() if trace else cell.end_to_end()
    folder = "layers" if trace else "end_to_end"
    metrics = {}
    for m in wanted:
        value = manifest.reader(folder, m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=peak_bytes)
    result = {
        "correct": verdict["correct"],
        "attempted": len(records),
        "failed": verdict["failed"],
        "metrics": metrics,
        "device": dev,
    }
    if traced:
        dev["busy_s"] = traced.busy_s
        dev["window_s"] = traced.window_s
        result["breakdown"] = {
            "device_ops": traced.top_ops(10),
            "idle_gaps": traced.top_gaps(10),
        }
    result["workload"] = cell.name
    result["seed"] = seed
    result["compared"] = verdict["compared"]
    return result


def emit(result: dict) -> None:
    """The numbers compared, each beside its limit, as the last lines on
    standard error; the result as the last line on standard output."""
    for name, c in result["compared"].items():
        print(f"compared {name}: value {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
