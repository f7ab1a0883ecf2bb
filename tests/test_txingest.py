"""Batched transaction-ingestion pipeline (ISSUE 6, cometbft_tpu/txingest/
— docs/tx-ingest.md).

The load-bearing test is the differential: batched admission (ingest
coalescer + ``check_tx_batch`` + one ``check_txs`` round trip + bulk-class
signature verification) must produce the same mempool contents, tx order
and CheckTx codes as sequential per-tx ``check_tx`` on randomized
valid/invalid/duplicate/oversize mixes — including with the
``COMETBFT_TPU_TXINGEST=0`` kill switch and under ``FaultyBackend``
injection (infrastructure failures degrade down the supervisor chain and
must never become rejected txs).

Everything runs on the supervisor's host-oracle device-runner seam (the
PR-3/PR-5 pattern): a real XLA-CPU dispatch costs ~1.7 s on the throttled
CI host, and every admission mechanism under test sits above that seam.
"""

import hashlib
import random
import threading

import numpy as np
import pytest

from cometbft_tpu import verifysched
from cometbft_tpu.abci import types as at
from cometbft_tpu.abci.application import Application
from cometbft_tpu.abci.kvstore import KVStoreApplication
from cometbft_tpu.config.config import MempoolConfig
from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.crypto import keys as ck
from cometbft_tpu.crypto import sigcache
from cometbft_tpu.crypto.secp256k1 import Secp256k1PrivKey
from cometbft_tpu.mempool.clist_mempool import (
    CListMempool,
    LRUTxCache,
    MempoolError,
    MempoolFullError,
    PreCheckError,
    TxInCacheError,
    TxTooLargeError,
)
from cometbft_tpu.mempool.reactor import MempoolReactor
from cometbft_tpu.ops import supervisor
from cometbft_tpu.proxy.multi_app_conn import AppConns, local_client_creator
from cometbft_tpu.txingest import (
    CODE_BAD_ENVELOPE,
    CODE_BAD_SIGNATURE,
    CODE_STALE_NONCE,
    CODESPACE,
    IngestCoalescer,
    SigVerifyingApp,
    sign_tx,
)
from cometbft_tpu.txingest import envelope as ev
from cometbft_tpu.txingest import stats as istats

ED_PRIVS = [
    ck.Ed25519PrivKey.from_seed(hashlib.sha256(b"ti%d" % i).digest())
    for i in range(3)
]
SECP_PRIV = Secp256k1PrivKey.from_secret(b"\x51" * 32)


def _oracle_runner(backend, pubs, msgs, sigs, lanes):
    out = np.zeros(lanes, dtype=bool)
    out[: len(pubs)] = [
        ref.verify_zip215(p, m, s) for p, m, s in zip(pubs, msgs, sigs)
    ]
    return out


@pytest.fixture
def clean_stats():
    istats.reset()
    yield
    istats.reset()


@pytest.fixture
def ingest_env(monkeypatch, clean_stats):
    """Pipeline-active environment: trusted tpu backend (so the ingest
    gate and the verify scheduler open) on the host-oracle device runner;
    clean scheduler/caches; full teardown."""
    from cometbft_tpu.crypto import backend_health

    monkeypatch.setenv("COMETBFT_TPU_CRYPTO_BACKEND", "tpu")
    monkeypatch.delenv("COMETBFT_TPU_TXINGEST", raising=False)
    monkeypatch.delenv("COMETBFT_TPU_VERIFY_SCHED", raising=False)
    supervisor.set_device_runner(_oracle_runner)
    sigcache.reset_cache()
    backend_health.reset()
    verifysched.reset_scheduler()
    verifysched.stats.reset()
    yield
    verifysched.reset_scheduler()
    supervisor.clear_device_runner()
    supervisor.clear_fault_injector()
    backend_health.reset()
    sigcache.reset_cache()
    verifysched.stats.reset()


class CountingConn:
    """Mempool-connection wrapper counting round trips by kind."""

    def __init__(self, inner):
        self.inner = inner
        self.check_tx_calls = 0
        self.check_txs_calls = 0

    def check_tx(self, req):
        self.check_tx_calls += 1
        return self.inner.check_tx(req)

    def check_txs(self, reqs):
        self.check_txs_calls += 1
        return self.inner.check_txs(reqs)


def _stack(app=None, envelope_aware=None, count=False, **cfg):
    """(conn, mempool) over a local-client SigVerifyingApp(kvstore)."""
    app = app if app is not None else SigVerifyingApp(KVStoreApplication())
    conns = AppConns(local_client_creator(app))
    conns.start()
    if envelope_aware is None:
        envelope_aware = getattr(
            conns.query.info(), "envelope_sig_verified", False
        )
    conn = CountingConn(conns.mempool) if count else conns.mempool
    mp = CListMempool(
        MempoolConfig(recheck=False, **cfg), conn,
        envelope_aware=envelope_aware,
    )
    return conn, mp


def _valid_ed(i: int, tag: bytes = b"k") -> bytes:
    return sign_tx(
        ED_PRIVS[i % len(ED_PRIVS)], b"%s%d=%d" % (tag, i, i), nonce=i
    )


def _forged(i: int) -> bytes:
    e = ev.decode(_valid_ed(i, tag=b"f"))
    return ev.encode(
        ev.Envelope(e.key_type, e.pubkey, e.nonce + 7, e.payload, e.signature)
    )


def _random_mix(rng: random.Random, n: int, max_tx_bytes: int) -> list:
    kinds = (
        "ed", "ed", "ed", "secp", "forged", "malformed",
        "plain_ok", "plain_bad", "oversize", "dup",
    )
    txs: list = []
    for i in range(n):
        kind = rng.choice(kinds)
        if kind == "dup" and txs:
            txs.append(txs[rng.randrange(len(txs))])
        elif kind == "ed":
            txs.append(_valid_ed(i))
        elif kind == "secp":
            txs.append(sign_tx(SECP_PRIV, b"s%d=%d" % (i, i), nonce=i))
        elif kind == "forged":
            txs.append(_forged(i))
        elif kind == "malformed":
            txs.append(ev.MAGIC + b"\x99junk%d" % i)
        elif kind == "plain_ok":
            txs.append(b"p%d=%d" % (i, i))
        elif kind == "plain_bad":
            txs.append(b"notakv%d" % i)  # kvstore: code 1
        else:  # oversize (or dup with nothing to duplicate)
            txs.append(
                sign_tx(
                    ED_PRIVS[0],
                    b"o%d=" % i + b"z" * (max_tx_bytes + 64),
                    nonce=i,
                )
            )
    return txs


def _outcome(res) -> tuple:
    if isinstance(res, at.CheckTxResponse):
        return ("resp", res.code, res.codespace, res.log)
    return ("err", type(res).__name__)


def _admit_per_tx(mp, txs) -> list:
    out = []
    for tx in txs:
        try:
            out.append(_outcome(mp.check_tx(tx)))
        except MempoolError as e:
            out.append(_outcome(e))
    return out


def _mempool_state(mp) -> tuple:
    return (mp.reap_max_txs(-1), mp.size(), mp.size_bytes())


# ---------------------------------------------------------------------------
# envelope codec
# ---------------------------------------------------------------------------


class TestEnvelope:
    def test_roundtrip_ed25519(self):
        tx = sign_tx(ED_PRIVS[0], b"a=1", nonce=42)
        assert ev.is_envelope(tx)
        e = ev.decode(tx)
        assert e.key_type == ev.KEY_ED25519
        assert e.nonce == 42
        assert e.payload == b"a=1"
        assert ev.encode(e) == tx
        assert ev.verify_envelopes([e]) == [True]

    def test_roundtrip_secp256k1(self):
        tx = sign_tx(SECP_PRIV, b"b=2", nonce=7)
        e = ev.decode(tx)
        assert e.key_type == ev.KEY_SECP256K1
        assert len(e.pubkey) == 33
        assert ev.verify_envelopes([e]) == [True]

    def test_plain_txs_are_not_envelopes(self):
        for tx in (b"", b"a=1", b"notakv", b"\x00\x01", ev.MAGIC[:3]):
            assert not ev.is_envelope(tx)
        with pytest.raises(ev.EnvelopeError, match="magic"):
            ev.decode(b"a=1")

    @pytest.mark.parametrize(
        "tx,match",
        [
            (ev.MAGIC, "truncated envelope header"),
            (ev.MAGIC + b"\x99" + b"x" * 120, "unknown key type"),
            (ev.MAGIC + b"\x01" + b"\x00" * 8 + b"short", "truncated"),
        ],
    )
    def test_malformed(self, tx, match):
        with pytest.raises(ev.EnvelopeError, match=match):
            ev.decode(tx)

    def test_signature_binds_key_type_nonce_and_payload(self):
        e = ev.decode(sign_tx(ED_PRIVS[0], b"a=1", nonce=1))
        for twisted in (
            ev.Envelope(e.key_type, e.pubkey, 2, e.payload, e.signature),
            ev.Envelope(e.key_type, e.pubkey, e.nonce, b"a=2", e.signature),
        ):
            assert ev.verify_envelopes([twisted]) == [False]

    def test_verify_envelopes_mixed_with_placeholders(self):
        good = ev.decode(_valid_ed(0))
        bad = ev.decode(_forged(1))
        assert ev.verify_envelopes([None, good, bad, None, good]) == [
            False, True, False, False, True,
        ]
        assert ev.verify_envelopes([]) == []

    def test_encode_validates(self):
        with pytest.raises(ev.EnvelopeError):
            ev.encode(ev.Envelope(0x77, b"\x00" * 32, 0, b"", b"\x00" * 64))
        with pytest.raises(ev.EnvelopeError):
            ev.encode(
                ev.Envelope(ev.KEY_ED25519, b"\x00" * 31, 0, b"", b"\x00" * 64)
            )
        with pytest.raises(ev.EnvelopeError):
            ev.encode(
                ev.Envelope(ev.KEY_ED25519, b"\x00" * 32, -1, b"", b"\x00" * 64)
            )


# ---------------------------------------------------------------------------
# SigVerifyingApp middleware
# ---------------------------------------------------------------------------


class RecordingApp(Application):
    """Inner app recording the payloads it sees; rejects payloads in
    ``reject`` with code 9."""

    def __init__(self):
        self.checked: list = []
        self.finalized: list = []
        self.reject: set = set()
        self.check_txs_calls = 0

    def info(self, req):
        return at.InfoResponse()

    def check_tx(self, req):
        self.checked.append(req.tx)
        if req.tx in self.reject:
            return at.CheckTxResponse(code=9, log="app says no")
        return at.CheckTxResponse(code=at.CODE_TYPE_OK)

    def check_txs(self, req):
        self.check_txs_calls += 1
        return super().check_txs(req)

    def prepare_proposal(self, req):
        return at.PrepareProposalResponse(txs=list(req.txs))

    def process_proposal(self, req):
        return at.ProcessProposalResponse(status=at.PROPOSAL_STATUS_ACCEPT)

    def finalize_block(self, req):
        self.finalized.append(list(req.txs))
        return at.FinalizeBlockResponse(
            tx_results=[at.ExecTxResult(code=0) for _ in req.txs]
        )


class TestSigVerifyingApp:
    def test_info_advertises_envelope_verification(self):
        assert SigVerifyingApp(KVStoreApplication()).info(
            at.InfoRequest()
        ).envelope_sig_verified is True

    def test_check_tx_unwraps_payload(self):
        inner = RecordingApp()
        app = SigVerifyingApp(inner)
        res = app.check_tx(at.CheckTxRequest(tx=_valid_ed(0)))
        assert res.ok
        assert inner.checked == [b"k0=0"]

    def test_check_tx_plain_passthrough_and_require_envelope(self):
        inner = RecordingApp()
        assert SigVerifyingApp(inner).check_tx(
            at.CheckTxRequest(tx=b"p=1")
        ).ok
        assert inner.checked == [b"p=1"]
        res = SigVerifyingApp(inner, require_envelope=True).check_tx(
            at.CheckTxRequest(tx=b"p=1")
        )
        assert (res.code, res.codespace) == (CODE_BAD_ENVELOPE, CODESPACE)

    def test_check_tx_rejects_forged_and_malformed(self):
        app = SigVerifyingApp(RecordingApp())
        res = app.check_tx(at.CheckTxRequest(tx=_forged(3)))
        assert (res.code, res.codespace) == (CODE_BAD_SIGNATURE, CODESPACE)
        res = app.check_tx(at.CheckTxRequest(tx=ev.MAGIC + b"\x99x" * 20))
        assert (res.code, res.codespace) == (CODE_BAD_ENVELOPE, CODESPACE)

    def test_check_txs_one_inner_batch_index_aligned(self):
        inner = RecordingApp()
        app = SigVerifyingApp(inner)
        reqs = [
            at.CheckTxRequest(tx=t)
            for t in (
                _valid_ed(0), _forged(1), b"plain=1",
                ev.MAGIC + b"\x99bad" * 8, _valid_ed(2),
            )
        ]
        resp = app.check_txs(at.CheckTxsRequest(requests=reqs))
        codes = [r.code for r in resp.responses]
        assert codes == [0, CODE_BAD_SIGNATURE, 0, CODE_BAD_ENVELOPE, 0]
        # one inner batch carried only the survivors' payloads
        assert inner.check_txs_calls == 1
        assert inner.checked == [b"k0=0", b"plain=1", b"k2=2"]

    def test_prepare_proposal_rewraps_envelopes(self):
        inner = RecordingApp()
        app = SigVerifyingApp(inner)
        e0, e1 = _valid_ed(0), _valid_ed(1)
        out = app.prepare_proposal(
            at.PrepareProposalRequest(max_tx_bytes=-1, txs=[e0, b"p=1", e1])
        )
        assert out.txs == [e0, b"p=1", e1]

    def test_prepare_proposal_duplicate_payloads_map_in_order(self):
        inner = RecordingApp()
        app = SigVerifyingApp(inner)
        # two different envelopes (nonces) carrying the same payload
        a = sign_tx(ED_PRIVS[0], b"same=1", nonce=1)
        b = sign_tx(ED_PRIVS[0], b"same=1", nonce=2)
        out = app.prepare_proposal(
            at.PrepareProposalRequest(max_tx_bytes=-1, txs=[a, b])
        )
        assert out.txs == [a, b]

    def test_process_proposal_rejects_forged(self):
        app = SigVerifyingApp(RecordingApp())
        ok = app.process_proposal(
            at.ProcessProposalRequest(txs=[_valid_ed(0), b"p=1"])
        )
        assert ok.status == at.PROPOSAL_STATUS_ACCEPT
        for bad in (_forged(1), ev.MAGIC + b"\x99zz" * 9):
            res = app.process_proposal(
                at.ProcessProposalRequest(txs=[_valid_ed(0), bad])
            )
            assert res.status == at.PROPOSAL_STATUS_REJECT

    def test_finalize_block_never_executes_bad_envelopes(self):
        inner = RecordingApp()
        app = SigVerifyingApp(inner)
        res = app.finalize_block(
            at.FinalizeBlockRequest(
                txs=[_valid_ed(0), _forged(1), b"p=1"]
            )
        )
        codes = [r.code for r in res.tx_results]
        assert codes == [0, CODE_BAD_SIGNATURE, 0]
        assert inner.finalized == [[b"k0=0", b"p=1"]]  # forged never ran


# ---------------------------------------------------------------------------
# the differential: batched admission == per-tx admission
# ---------------------------------------------------------------------------


class TestBatchedAdmissionDifferential:
    MAX_TX = 512

    def _compare(self, txs, via_coalescer=False, **cfg):
        cfg.setdefault("max_tx_bytes", self.MAX_TX)
        _, mp_seq = _stack(**cfg)
        seq = _admit_per_tx(mp_seq, txs)

        conn_b, mp_bat = _stack(count=True, **cfg)
        if via_coalescer:
            results: dict = {}
            order: list = []

            def note(sender, res, _r=results, _o=order):
                _r[len(_o)] = res
                _o.append(res)

            ing = IngestCoalescer(
                mp_bat, batch_max=16, queue_cap=len(txs) + 1,
                start_thread=False, on_result=note,
            )
            bat = []
            for tx in txs:
                try:
                    r = ing.submit(tx, sender="")
                except MempoolError as e:
                    bat.append(_outcome(e))
                    continue
                if r is None:
                    bat.append(None)  # placeholder: resolved at flush
                else:
                    bat.append(_outcome(r))
            ing.flush_now()
            it = iter(order)
            bat = [b if b is not None else _outcome(next(it)) for b in bat]
        else:
            bat = [_outcome(r) for r in mp_bat.check_tx_batch(txs)]
        assert bat == seq
        assert _mempool_state(mp_bat) == _mempool_state(mp_seq)
        return conn_b

    @pytest.mark.parametrize("seed", [1, 7, 23])
    def test_randomized_mix_host_path(self, seed, clean_stats):
        rng = random.Random(seed)
        self._compare(_random_mix(rng, 64, self.MAX_TX))

    @pytest.mark.parametrize("seed", [5, 11])
    def test_randomized_mix_scheduler_path(self, seed, ingest_env):
        """Same differential with the verify scheduler active: envelope
        signatures ride the bulk class through the oracle seam."""
        rng = random.Random(seed)
        conn = self._compare(
            _random_mix(rng, 48, self.MAX_TX), via_coalescer=True
        )
        # the batching win this subsystem exists for: far fewer app round
        # trips than txs (some per-tx calls remain: duplicate-of-rejected
        # re-checks)
        assert conn.check_txs_calls <= 4
        assert conn.check_tx_calls <= 8

    def test_kill_switch_restores_per_tx_path(self, monkeypatch, ingest_env):
        # trusted backend (host-oracle seam, via the fixture) so ONLY the
        # kill switch — not the backend gate — is what disables the pipeline
        monkeypatch.setenv("COMETBFT_TPU_TXINGEST", "0")
        txs = _random_mix(random.Random(3), 32, self.MAX_TX)
        _, mp_seq = _stack(max_tx_bytes=self.MAX_TX)
        seq = _admit_per_tx(mp_seq, txs)

        conn, mp = _stack(count=True, max_tx_bytes=self.MAX_TX)
        ing = IngestCoalescer(mp, start_thread=False)
        assert not ing.active()
        bat = []
        for tx in txs:
            try:
                bat.append(_outcome(ing.submit(tx)))
            except MempoolError as e:
                bat.append(_outcome(e))
        assert bat == seq
        assert _mempool_state(mp) == _mempool_state(mp_seq)
        # bit-for-bit the old shape: one check_tx round trip per non-dup
        # tx, zero batched calls, nothing ever queued
        assert conn.check_txs_calls == 0
        assert ing.pending() == 0

    def test_faulty_backend_never_rejects_txs(self, ingest_env):
        """Acceptance criterion: device-infrastructure failures degrade
        down the supervisor chain (device -> host) and produce the same
        verdicts — a raise/wrong-shape backend must never surface as
        CheckTx rejections or dropped txs."""
        from cometbft_tpu.crypto import backend_health

        txs = _random_mix(random.Random(13), 40, self.MAX_TX)
        _, mp_clean = _stack(max_tx_bytes=self.MAX_TX)
        clean = _admit_per_tx(mp_clean, txs)
        for mode in ("raise", "wrong_shape"):
            # the clean pass populated the signature cache; drop it so the
            # faulty passes really dispatch through the injector
            sigcache.reset_cache()
            supervisor.set_fault_injector(supervisor.FaultyBackend(mode))
            try:
                _, mp = _stack(max_tx_bytes=self.MAX_TX)
                bat = [_outcome(r) for r in mp.check_tx_batch(txs)]
            finally:
                supervisor.clear_fault_injector()
            assert bat == clean, mode
            assert _mempool_state(mp) == _mempool_state(mp_clean)
            snap = backend_health.snapshot()
            assert snap["fallback_signatures"] > 0  # the chain really fired
            backend_health.reset()

    def test_duplicate_of_rejected_tx_is_rechecked(self, clean_stats):
        """Sequential semantics for the nasty case: a rejected tx releases
        its cache slot, so a later in-batch duplicate gets a full re-check
        (not TxInCacheError)."""
        forged = _forged(2)
        txs = [_valid_ed(0), forged, forged, _valid_ed(0)]
        self._compare(txs)

    def test_mempool_full_parity(self, clean_stats):
        txs = [_valid_ed(i) for i in range(12)]
        self._compare(txs, size=5)

    def test_pre_check_parity(self, clean_stats):
        def pre(tx: bytes):
            return "envelopes only" if not ev.is_envelope(tx) else None

        txs = [_valid_ed(0), b"plain=1", _valid_ed(1)]
        _, mp_seq = _stack(max_tx_bytes=self.MAX_TX)
        mp_seq.pre_check = pre
        seq = _admit_per_tx(mp_seq, txs)
        _, mp = _stack(max_tx_bytes=self.MAX_TX)
        mp.pre_check = pre
        bat = [_outcome(r) for r in mp.check_tx_batch(txs)]
        assert bat == seq
        assert seq[1] == ("err", "PreCheckError")


# ---------------------------------------------------------------------------
# batched recheck
# ---------------------------------------------------------------------------


class TestBatchedRecheck:
    def _recheck_stack(self, monkeypatch, enabled: bool):
        monkeypatch.setenv(
            "COMETBFT_TPU_TXINGEST", "1" if enabled else "0"
        )
        inner = RecordingApp()
        conns = AppConns(local_client_creator(SigVerifyingApp(inner)))
        conns.start()
        conn = CountingConn(conns.mempool)
        mp = CListMempool(
            MempoolConfig(recheck=True), conn, envelope_aware=True
        )
        txs = [_valid_ed(i) for i in range(5)]
        for tx in txs:
            assert mp.check_tx(tx).ok
        return inner, conn, mp, txs

    @pytest.mark.parametrize("enabled", [True, False])
    def test_recheck_verdict_parity(self, monkeypatch, clean_stats, enabled):
        inner, conn, mp, txs = self._recheck_stack(monkeypatch, enabled)
        # commit tx0; app starts rejecting tx2's payload on recheck
        inner.reject.add(b"k2=2")
        before = conn.check_tx_calls
        mp.update(1, [txs[0]], [at.ExecTxResult(code=0)])
        remaining = mp.reap_max_txs(-1)
        assert remaining == [txs[1], txs[3], txs[4]]  # tx2 rechecked out
        assert mp.size() == 3
        if enabled:
            assert conn.check_txs_calls == 1  # ONE batched round trip
            assert conn.check_tx_calls == before
        else:
            assert conn.check_txs_calls == 0
            assert conn.check_tx_calls == before + 4

    def test_recheck_stats(self, monkeypatch, clean_stats):
        self._recheck_stack(monkeypatch, True)[2].update(
            1, [], []
        )
        snap = istats.snapshot()
        assert snap["recheck_batches"] == 1
        assert snap["recheck_txs"] == 5


# ---------------------------------------------------------------------------
# ingest coalescer
# ---------------------------------------------------------------------------


class TestIngestCoalescer:
    def test_inactive_without_trusted_backend(self, monkeypatch, clean_stats):
        monkeypatch.setenv("COMETBFT_TPU_CRYPTO_BACKEND", "cpu")
        _, mp = _stack()
        ing = IngestCoalescer(mp, start_thread=False)
        assert not ing.active()
        res = ing.submit(_valid_ed(0))
        assert res is not None and res.ok  # synchronous passthrough
        assert ing.pending() == 0

    def test_queue_full_sheds_to_sync_path(self, ingest_env):
        _, mp = _stack()
        ing = IngestCoalescer(mp, queue_cap=2, start_thread=False)
        assert ing.submit(_valid_ed(0)) is None
        assert ing.submit(_valid_ed(1)) is None
        shed = ing.submit(_valid_ed(2))  # queue full: sync, still a verdict
        assert shed is not None and shed.ok
        assert istats.snapshot()["shed_to_sync"] == 1
        assert mp.size() == 1  # only the shed tx reached the mempool so far
        assert ing.flush_now() == 2
        assert mp.size() == 3

    def test_pre_queue_dedup_costs_no_slot(self, ingest_env):
        _, mp = _stack()
        assert mp.check_tx(_valid_ed(0)).ok  # cached via the per-tx path
        ing = IngestCoalescer(mp, start_thread=False)
        with pytest.raises(TxInCacheError):
            ing.submit(_valid_ed(0), sender="peerX")
        assert ing.pending() == 0
        assert istats.snapshot()["cache_hits"] == 1

    def test_flush_chunking_and_result_order(self, ingest_env):
        conn, mp = _stack(count=True)
        got: list = []
        ing = IngestCoalescer(
            mp, batch_max=4, queue_cap=64, start_thread=False,
            on_result=lambda s, r: got.append((s, _outcome(r))),
        )
        txs = [_valid_ed(i) for i in range(10)]
        for i, tx in enumerate(txs):
            assert ing.submit(tx, sender="p%d" % i) is None
        assert ing.flush_now() == 10
        assert [s for s, _ in got] == ["p%d" % i for i in range(10)]
        assert all(o[0] == "resp" and o[1] == 0 for _, o in got)
        assert conn.check_txs_calls == 3  # ceil(10 / 4)
        assert istats.snapshot()["flushes"] == 3

    def test_flusher_thread_deadline(self, ingest_env):
        _, mp = _stack()
        done = threading.Event()
        ing = IngestCoalescer(
            mp, flush_us=1000, queue_cap=64,
            on_result=lambda s, r: done.set(),
        )
        try:
            assert ing.submit(_valid_ed(0)) is None
            assert done.wait(10.0), "deadline flush never fired"
            assert mp.size() == 1
        finally:
            ing.close()

    def test_batch_failure_degrades_to_per_tx(self, monkeypatch, ingest_env):
        _, mp = _stack()
        got: list = []
        ing = IngestCoalescer(
            mp, start_thread=False,
            on_result=lambda s, r: got.append(_outcome(r)),
        )
        monkeypatch.setattr(
            mp, "check_tx_batch",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")),
        )
        assert ing.submit(_valid_ed(0)) is None
        assert ing.submit(_valid_ed(1)) is None
        ing.flush_now()  # must not raise; re-admits per-tx
        assert got == [("resp", 0, "", ""), ("resp", 0, "", "")]
        assert mp.size() == 2

    def test_close_drains_queue(self, ingest_env):
        _, mp = _stack()
        ing = IngestCoalescer(mp, start_thread=False)
        for i in range(3):
            assert ing.submit(_valid_ed(i)) is None
        ing.close()
        assert mp.size() == 3
        # post-close submissions degrade to sync, never vanish
        assert ing.submit(_valid_ed(9)) is not None
        assert mp.size() == 4

    def test_a_flood_sheds_no_consensus_verify(self, ingest_env):
        """A flood of signed txs queued whole and flushed in bulk-class
        batches while a consensus caller verifies votes on another thread:
        every vote verifies, no consensus-class item is shed, and every tx
        reaches the mempool."""
        conn, mp = _stack(count=True)
        n_txs = 64
        ing = IngestCoalescer(
            mp, batch_max=16, queue_cap=n_txs, start_thread=False
        )
        flood = [_valid_ed(i, tag=b"flood") for i in range(n_txs)]
        votes = [b"consensus-vote-%d" % i for i in range(24)]
        priv = ED_PRIVS[0]
        pub = priv.pub_key()
        verdicts: list = []
        shed0 = verifysched.stats.snapshot()["shed"]["consensus"]

        def voter():
            for msg in votes:
                verdicts.append(
                    verifysched.verify_cached(
                        pub, msg, priv.sign(msg),
                        priority=verifysched.PRIO_CONSENSUS,
                    )
                )

        th = threading.Thread(target=voter)
        th.start()
        try:
            for tx in flood:
                assert ing.submit(tx) is None  # a queue sized to the flood
            assert ing.flush_now() == n_txs
        finally:
            th.join(timeout=60)
        assert verdicts == [True] * len(votes)
        assert verifysched.stats.snapshot()["shed"]["consensus"] == shed0
        assert mp.size() == n_txs
        assert conn.check_txs_calls == n_txs // 16 and conn.check_tx_calls == 0


# ---------------------------------------------------------------------------
# LRUTxCache (previously untested seam the coalescer leans on)
# ---------------------------------------------------------------------------


class TestLRUTxCache:
    def test_eviction_order(self):
        c = LRUTxCache(3)
        for k in (b"a", b"b", b"c"):
            assert c.push(k)
        assert c.push(b"d")  # evicts a (oldest)
        assert not c.has(b"a")
        assert all(c.has(k) for k in (b"b", b"c", b"d"))

    def test_push_refreshes_recency(self):
        c = LRUTxCache(3)
        for k in (b"a", b"b", b"c"):
            c.push(k)
        assert not c.push(b"a")  # duplicate: refreshed, not re-added
        c.push(b"d")  # now b is oldest
        assert c.has(b"a") and not c.has(b"b")

    def test_touch_refreshes_recency(self):
        c = LRUTxCache(3)
        for k in (b"a", b"b", b"c"):
            c.push(k)
        assert c.touch(b"a")
        assert not c.touch(b"zz")
        c.push(b"d")
        assert c.has(b"a") and not c.has(b"b")

    def test_remove_and_reset(self):
        c = LRUTxCache(4)
        c.push(b"a")
        c.remove(b"a")
        assert not c.has(b"a")
        c.remove(b"a")  # idempotent
        c.push(b"a")
        c.reset()
        assert not c.has(b"a")

    def test_zero_size_never_evicts(self):
        c = LRUTxCache(0)
        for i in range(10):
            assert c.push(b"%d" % i)
        assert all(c.has(b"%d" % i) for i in range(10))

    def test_thread_safety_under_concurrent_mutation(self):
        c = LRUTxCache(64)
        errs: list = []

        def worker(seed: int):
            rng = random.Random(seed)
            try:
                for _ in range(2000):
                    k = b"k%d" % rng.randrange(128)
                    op = rng.randrange(4)
                    if op == 0:
                        c.push(k)
                    elif op == 1:
                        c.touch(k)
                    elif op == 2:
                        c.has(k)
                    else:
                        c.remove(k)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [
            threading.Thread(target=worker, args=(s,)) for s in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        assert len(c._map) <= 64


# ---------------------------------------------------------------------------
# lane round-robin reap priority (untested seam the coalescer leans on)
# ---------------------------------------------------------------------------


class LaneApp:
    """Mempool-connection stub assigning lanes by tx prefix."""

    def check_tx(self, req):
        lane = {b"f": "fast", b"m": "mid"}.get(req.tx[:1], "slow")
        return at.CheckTxResponse(code=at.CODE_TYPE_OK, lane_id=lane)


class TestLaneReapPriority:
    LANES = {"fast": 3, "mid": 2, "slow": 1}

    def _mp(self) -> CListMempool:
        return CListMempool(
            MempoolConfig(recheck=False), LaneApp(),
            lane_priorities=dict(self.LANES), default_lane="slow",
        )

    def test_round_robin_in_priority_order(self):
        mp = self._mp()
        for tx in (b"s1=1", b"s2=1", b"f1=1", b"m1=1", b"f2=1", b"m2=1"):
            mp.check_tx(tx)
        # one tx per lane per pass, highest priority lane first
        assert mp.reap_max_txs(-1) == [
            b"f1=1", b"m1=1", b"s1=1", b"f2=1", b"m2=1", b"s2=1",
        ]
        assert mp.reap_max_txs(4) == [b"f1=1", b"m1=1", b"s1=1", b"f2=1"]

    def test_reap_skips_removed_elements(self):
        mp = self._mp()
        for tx in (b"f1=1", b"f2=1", b"m1=1"):
            mp.check_tx(tx)
        mp.update(1, [b"f1=1"], [at.ExecTxResult(code=0)])
        assert mp.reap_max_txs(-1) == [b"f2=1", b"m1=1"]

    def test_unknown_lane_falls_back_to_default(self):
        mp = CListMempool(
            MempoolConfig(recheck=False), LaneApp(),
            lane_priorities={"other": 5, "slow": 1}, default_lane="slow",
        )
        mp.check_tx(b"f1=1")  # app says "fast", mempool has no such lane
        assert mp.reap_max_txs(-1) == [b"f1=1"]
        assert mp.lanes["slow"].front() is not None

    def test_batched_admission_preserves_lane_order(self, clean_stats):
        seq_mp, bat_mp = self._mp(), self._mp()
        txs = [b"s1=1", b"f1=1", b"m1=1", b"f2=1", b"s2=1", b"m2=1"]
        for tx in txs:
            seq_mp.check_tx(tx)
        bat_mp.check_tx_batch(txs)
        assert bat_mp.reap_max_txs(-1) == seq_mp.reap_max_txs(-1)


# ---------------------------------------------------------------------------
# mempool reactor: per-peer accounting
# ---------------------------------------------------------------------------


class FakePeer:
    def __init__(self, peer_id: str):
        self.id = peer_id


class _Logger:
    def __init__(self):
        self.lines: list = []

    def debug(self, msg, **kw):
        self.lines.append((msg, kw))

    info = error = warn = debug

    def with_(self, **kw):
        return self


class TestReactorAccounting:
    def _reactor(self, ingest=None):
        _, mp = _stack()
        log = _Logger()
        r = MempoolReactor(MempoolConfig(), mp, logger=log, ingest=ingest)
        return r, mp, log

    def test_counts_accept_dedup_reject_per_peer(self, clean_stats):
        r, mp, log = self._reactor()
        r.receive(0, FakePeer("p1"), _valid_ed(0))
        r.receive(0, FakePeer("p1"), _valid_ed(0))  # dup
        r.receive(0, FakePeer("p2"), _forged(1))  # CheckTx reject (code 102)
        r.receive(0, FakePeer("p2"), _valid_ed(2))
        stats = r.peer_ingest_stats()
        assert stats["p1"] == {
            "accepted": 1, "dedup": 1, "rejected": 0, "error": 0,
        }
        assert stats["p2"] == {
            "accepted": 1, "dedup": 0, "rejected": 1, "error": 0,
        }
        # rejections and dedups are logged, not swallowed
        assert any(m == "tx rejected by CheckTx" for m, _ in log.lines)
        assert any(m == "tx dedup (cache hit)" for m, _ in log.lines)

    def test_error_kinds_counted(self, clean_stats):
        r, mp, _ = self._reactor()
        r.receive(0, FakePeer("p1"), b"x" * (2 * 1024 * 1024))  # too large
        assert r.peer_ingest_stats()["p1"]["error"] == 1

    def test_flush_time_outcomes_flow_back(self, ingest_env):
        _, mp = _stack()
        ing = IngestCoalescer(mp, start_thread=False)
        r = MempoolReactor(MempoolConfig(), mp, logger=_Logger(), ingest=ing)
        assert ing.on_result is not None  # reactor wired itself in
        r.receive(0, FakePeer("p1"), _valid_ed(0))
        r.receive(0, FakePeer("p2"), _forged(1))
        assert ing.pending() == 2  # queued, no verdicts yet
        assert r.peer_ingest_stats() == {}
        ing.flush_now()
        stats = r.peer_ingest_stats()
        assert stats["p1"]["accepted"] == 1
        assert stats["p2"]["rejected"] == 1


# ---------------------------------------------------------------------------
# ABCI surface: batched CheckTx plumbing
# ---------------------------------------------------------------------------


class TestCheckTxsPlumbing:
    def test_application_default_loops_over_check_tx(self):
        inner = RecordingApp()
        resp = inner.check_txs(
            at.CheckTxsRequest(
                requests=[at.CheckTxRequest(tx=b"a"), at.CheckTxRequest(tx=b"b")]
            )
        )
        assert [r.code for r in resp.responses] == [0, 0]
        assert inner.checked == [b"a", b"b"]

    def test_local_client_batches(self):
        conns = AppConns(local_client_creator(RecordingApp()))
        conns.start()
        out = conns.mempool.check_txs(
            [at.CheckTxRequest(tx=b"a"), at.CheckTxRequest(tx=b"b")]
        )
        assert [r.code for r in out] == [0, 0]
        assert conns.mempool.check_txs([]) == []

    def test_local_client_loops_per_tx_for_default_apps(self):
        """A duck-typed app without the method — and any app on the
        base-class loop — goes straight to per-tx calls, releasing the
        shared connection lock between txs."""
        from cometbft_tpu.abci.client import LocalClient

        class LegacyApp:
            def echo(self, req):
                return at.EchoResponse(message=req)

            def check_tx(self, req):
                return at.CheckTxResponse(code=at.CODE_TYPE_OK)

        cl = LocalClient(LegacyApp())
        out = cl.check_txs([at.CheckTxRequest(tx=b"a")] * 3)
        assert len(out) == 3 and all(r.ok for r in out)

    def test_remote_client_falls_back_and_remembers(self):
        """A remote end that errors on the unknown batched frame degrades
        to per-tx calls, and the probe is not repeated."""
        from cometbft_tpu.abci.client import ABCIClientError, Client

        class LegacyRemote(Client):
            def __init__(self):
                self.calls: list = []

            def call(self, method, req):
                self.calls.append(method)
                if method == "check_txs":
                    raise ABCIClientError("unknown ABCI method check_txs")
                return at.CheckTxResponse(code=at.CODE_TYPE_OK)

        cl = LegacyRemote()
        out = cl.check_txs([at.CheckTxRequest(tx=b"a")] * 3)
        assert len(out) == 3 and all(r.ok for r in out)
        assert cl._no_check_txs  # remembered: next call skips the probe
        assert cl.check_txs([at.CheckTxRequest(tx=b"b")])[0].ok
        assert cl.calls.count("check_txs") == 1

    def test_app_bug_inside_check_txs_surfaces(self):
        """An AttributeError raised INSIDE an app's own check_txs override
        is a bug, not a missing method — it must not silently degrade the
        batch to a per-tx re-run."""
        from cometbft_tpu.abci.client import LocalClient

        class BuggyApp:
            def echo(self, req):
                return at.EchoResponse(message=req)

            def check_txs(self, req):
                raise AttributeError("typo'd field access")

        with pytest.raises(AttributeError, match="typo"):
            LocalClient(BuggyApp()).check_txs([at.CheckTxRequest(tx=b"a")])

    def test_client_rejects_miscounted_response(self):
        from cometbft_tpu.abci.client import ABCIClientError, LocalClient

        class BrokenApp:
            def echo(self, req):
                return at.EchoResponse(message=req)

            def check_txs(self, req):
                return at.CheckTxsResponse(responses=[])

        with pytest.raises(ABCIClientError, match="0 responses for 2"):
            LocalClient(BrokenApp()).check_txs(
                [at.CheckTxRequest(tx=b"a"), at.CheckTxRequest(tx=b"b")]
            )

    def test_codec_roundtrips_check_txs(self):
        import io

        from cometbft_tpu.abci import codec

        req = at.CheckTxsRequest(
            requests=[at.CheckTxRequest(tx=b"a", type_=1)]
        )
        buf = io.BytesIO(codec.encode_request("check_txs", req))
        method, back = codec.read_request(buf)
        assert method == "check_txs"
        assert back.requests[0].tx == b"a"
        assert back.requests[0].type_ == 1
        resp = at.CheckTxsResponse(
            responses=[at.CheckTxResponse(code=5, codespace="x")]
        )
        buf = io.BytesIO(codec.encode_response("check_txs", resp))
        method, back = codec.read_response(buf)
        assert method == "check_txs"
        assert back.responses[0].code == 5


# ---------------------------------------------------------------------------
# metrics exposition
# ---------------------------------------------------------------------------


class TestMetricsExposition:
    def test_mempool_counters_scrape_without_jax(self, clean_stats):
        from cometbft_tpu.libs.metrics import NodeMetrics

        _, mp = _stack(max_tx_bytes=512)
        mp.check_tx_batch(
            [_valid_ed(0), _valid_ed(0), _forged(1), b"p=1"]
        )
        page = NodeMetrics("testti").registry.expose()
        assert "testti_mempool_cache_hits 1" in page
        assert "testti_mempool_cache_misses 3" in page
        assert "testti_mempool_admitted_txs 2" in page
        assert "testti_mempool_checktx_batches 1" in page
        assert (
            'testti_mempool_rejected_txs{code="%d"} 1' % CODE_BAD_SIGNATURE
            in page
        )
        assert 'testti_mempool_admission_errors{kind="duplicate"} 1' in page
        assert "testti_mempool_ingest_queue_depth 0" in page
        assert "testti_mempool_sig_prechecked" in page
        assert "testti_mempool_ingest_batch_occupancy" in page

    def test_stats_snapshot_derived_fields(self, clean_stats):
        istats.record_cache(True)
        istats.record_cache(False)
        istats.record_flush(12, 16)
        snap = istats.snapshot()
        assert snap["cache_hit_rate"] == 0.5
        assert snap["batch_occupancy"] == 0.75
        istats.reset()
        assert istats.snapshot()["flushes"] == 0


# ---------------------------------------------------------------------------
# per-sender nonce replay protection (coalescer last-verified-nonce LRU)
# ---------------------------------------------------------------------------


class TestNonceReplayProtection:
    """Replayed / re-signed envelopes at or below a sender's last VERIFIED
    nonce die at ingest with the canonical ``CODE_STALE_NONCE`` — before a
    queue slot, a signature check, or an app round trip.  Only verified
    nonces are recorded, so forged envelopes cannot poison a sender."""

    def _ing(self, **kw):
        _, mp = _stack(max_tx_bytes=512)
        ing = IngestCoalescer(mp, start_thread=False, **kw)
        return mp, ing

    def _admit(self, ing, tx):
        res = ing.submit(tx, sender="peer")
        if res is None:
            ing.flush_now()
        return res

    def test_replay_below_verified_nonce_rejected(self, ingest_env):
        mp, ing = self._ing()
        assert self._admit(ing, sign_tx(ED_PRIVS[0], b"a=1", nonce=5)) is None
        # fresh payload re-signed under an old nonce: canonical 103
        res = ing.submit(sign_tx(ED_PRIVS[0], b"a=2", nonce=5), sender="peer")
        assert res is not None and res.code == CODE_STALE_NONCE
        assert res.codespace == CODESPACE
        res = ing.submit(sign_tx(ED_PRIVS[0], b"a=3", nonce=4), sender="peer")
        assert res.code == CODE_STALE_NONCE
        # the mempool never saw either replay
        assert mp.size() == 1
        snap = istats.snapshot()
        assert snap["rejected"].get(str(CODE_STALE_NONCE), 0) == 2
        assert snap["errors"].get("stale_nonce", 0) == 2
        # a genuinely fresh nonce still admits
        assert self._admit(ing, sign_tx(ED_PRIVS[0], b"a=4", nonce=6)) is None
        ing.flush_now()
        assert mp.size() == 2

    def test_forged_high_nonce_cannot_poison_sender(self, ingest_env):
        mp, ing = self._ing()
        good = sign_tx(ED_PRIVS[0], b"k=1", nonce=1)
        e = ev.decode(good)
        forged = ev.encode(
            ev.Envelope(e.key_type, e.pubkey, 10_000, e.payload, e.signature)
        )
        assert self._admit(ing, forged) is None  # queued, rejected at flush
        # the forgery was rejected with 102 and its nonce NOT recorded:
        assert self._admit(ing, good) is None
        ing.flush_now()
        assert mp.size() == 1  # the honest tx made it in
        snap = istats.snapshot()
        assert snap["rejected"].get(str(CODE_BAD_SIGNATURE), 0) == 1
        assert snap["rejected"].get(str(CODE_STALE_NONCE), 0) == 0

    def test_shed_to_sync_path_also_records_nonces(self, ingest_env):
        mp, ing = self._ing(queue_cap=1)
        ing.submit(sign_tx(ED_PRIVS[1], b"q=0", nonce=3), sender="p")  # queued
        # queue full -> synchronous path; its verified nonce must count
        res = ing.submit(sign_tx(ED_PRIVS[0], b"s=1", nonce=7), sender="p")
        assert res is not None and res.ok
        stale = ing.submit(sign_tx(ED_PRIVS[0], b"s=2", nonce=7), sender="p")
        assert stale.code == CODE_STALE_NONCE
        ing.flush_now()

    def test_lru_eviction_forgets_oldest_sender(self, monkeypatch, ingest_env):
        monkeypatch.setenv("COMETBFT_TPU_TXINGEST_NONCES", "1")
        mp, ing = self._ing()
        assert self._admit(ing, sign_tx(ED_PRIVS[0], b"x=1", nonce=5)) is None
        assert self._admit(ing, sign_tx(ED_PRIVS[1], b"y=1", nonce=5)) is None
        # sender 0 was evicted from the 1-slot LRU: its replay now reaches
        # the app (bounded memory beats perfect replay recall)
        res = ing.submit(sign_tx(ED_PRIVS[0], b"x=2", nonce=5), sender="p")
        assert res is None
        ing.flush_now()

    def test_plain_and_malformed_txs_bypass_nonce_check(self, ingest_env):
        mp, ing = self._ing()
        assert self._admit(ing, b"plain=1") is None
        bad = ev.MAGIC + b"\x99junk"
        assert self._admit(ing, bad) is None  # malformed: canonical 101 path
        snap = istats.snapshot()
        assert snap["rejected"].get(str(CODE_STALE_NONCE), 0) == 0

    def test_inactive_pipeline_skips_nonce_check(self, monkeypatch, clean_stats):
        monkeypatch.setenv("COMETBFT_TPU_TXINGEST", "0")
        mp, ing = self._ing()
        tx1 = sign_tx(ED_PRIVS[0], b"z=1", nonce=5)
        tx2 = sign_tx(ED_PRIVS[0], b"z=2", nonce=5)
        assert ing.submit(tx1, sender="p") is not None  # sync passthrough
        res = ing.submit(tx2, sender="p")
        # kill switch restores per-tx behavior bit-for-bit: no 103
        assert res is None or res.code != CODE_STALE_NONCE
