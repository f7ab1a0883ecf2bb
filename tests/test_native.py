"""C++ native component tests: differential against the Python paths
(the Python implementations are the correctness oracles)."""

import ctypes
import hashlib
import os
import random

import numpy as np
import pytest

from cometbft_tpu import native

L = 2**252 + 27742317777372353535851937790883648493


@pytest.fixture(scope="module")
def nlib():
    lib = native.lib()
    if lib is None:
        pytest.skip("native library unavailable")
    return lib


class TestSha512:
    def test_differential(self, nlib):
        rng = random.Random(1)
        cases = [b"", b"a", b"abc", bytes(127), bytes(128), bytes(129)]
        cases += [rng.randbytes(rng.randrange(0, 5000)) for _ in range(50)]
        for msg in cases:
            out = ctypes.create_string_buffer(64)
            nlib.sha512(msg, len(msg), out)
            assert out.raw == hashlib.sha512(msg).digest()


class TestPacker:
    def test_differential_mod_l(self, nlib):
        rng = random.Random(2)
        n = 300
        pubs = [rng.randbytes(32) for _ in range(n)]
        msgs = [rng.randbytes(rng.randrange(0, 300)) for _ in range(n)]
        sigs = []
        for i in range(n):
            r = rng.randbytes(32)
            if i % 5 == 0:
                s = (L + rng.randrange(0, 2**120)).to_bytes(32, "little")
            elif i % 11 == 0:
                s = bytes(32)  # s = 0 edge
            else:
                s = rng.randrange(0, L).to_bytes(32, "little")
            sigs.append(r + s)
        off = [0]
        for m in msgs:
            off.append(off[-1] + len(m))
        off_arr = (ctypes.c_int64 * (n + 1))(*off)
        s_out = ctypes.create_string_buffer(n * 32)
        m_out = ctypes.create_string_buffer(n * 32)
        ok_out = ctypes.create_string_buffer(n)
        rc = nlib.ed25519_pack(
            b"".join(pubs), b"".join(sigs), b"".join(msgs), off_arr, n,
            s_out, m_out, ok_out,
        )
        assert rc == 0
        for i in range(n):
            s = int.from_bytes(sigs[i][32:], "little")
            assert ok_out.raw[i] == int(s < L)
            h = (
                int.from_bytes(
                    hashlib.sha512(sigs[i][:32] + pubs[i] + msgs[i]).digest(),
                    "little",
                )
                % L
            )
            want_m = (L - h) % L
            assert (
                int.from_bytes(m_out.raw[i * 32 : (i + 1) * 32], "little")
                == want_m
            ), i

    def test_prepare_batch_native_vs_python(self, nlib):
        """ops.verify.prepare_batch: native path == Python fallback."""
        from cometbft_tpu.crypto import ed25519_ref as ref
        from cometbft_tpu.ops import verify as ov

        pubs, msgs, sigs = [], [], []
        for i in range(40):
            seed = hashlib.sha256(b"nat%d" % i).digest()
            pubs.append(ref.pubkey_from_seed(seed))
            msgs.append(b"native-diff-%d" % i)
            sigs.append(ref.sign(seed, msgs[-1]))
        # a structurally broken entry
        pubs.append(b"short")
        msgs.append(b"x")
        sigs.append(b"y" * 64)

        native_arrays, n1, st1 = ov.prepare_batch(pubs, msgs, sigs)
        os.environ["COMETBFT_TPU_NO_NATIVE"] = "1"
        try:
            native._tried = False
            native._lib = None
            py_arrays, n2, st2 = ov.prepare_batch(pubs, msgs, sigs)
        finally:
            del os.environ["COMETBFT_TPU_NO_NATIVE"]
            native._tried = False
            native._lib = None
        assert n1 == n2
        assert (st1 == st2).all()
        for k in native_arrays:
            assert np.array_equal(
                np.asarray(native_arrays[k]), np.asarray(py_arrays[k])
            ), k


class TestNativeWAL:
    def test_native_frames_readable_by_python(self, nlib, tmp_path):
        from cometbft_tpu.consensus.wal import WAL

        path = str(tmp_path / "nat.wal")
        w = WAL(path)
        assert w._nh is not None, "native WAL engine not active"
        w.write(b"rec-one")
        w.write_sync(b"rec-two")
        w.write_end_height(7)
        w.write(b"rec-after")
        w.close()

        r = WAL(path)
        recs = list(r.iter_records())
        payloads = [rec.payload for rec in recs if rec.kind == 1]
        assert payloads == [b"rec-one", b"rec-two", b"rec-after"]
        assert any(rec.end_height == 7 for rec in recs)
        assert r.replay_after_height(7) == [b"rec-after"]
        r.close()

    def test_rotation(self, nlib, tmp_path):
        from cometbft_tpu.consensus.wal import WAL

        path = str(tmp_path / "rot.wal")
        w = WAL(path, head_size_limit=1024)
        for i in range(100):
            w.write(b"payload-%03d" % i * 8)
        w.close()
        assert os.path.exists(path + ".000")
        r = WAL(path, head_size_limit=1024)
        recs = [rec.payload for rec in r.iter_records()]
        assert len(recs) == 100
        assert recs[0] == b"payload-000" * 8
        assert recs[-1] == b"payload-099" * 8
        r.close()


class TestCommitSignBytes:
    """The C++ canonical sign-bytes builder must be byte-exact with the
    python encoder (types/canonical.py) for every flag/timestamp shape."""

    def _commit(self, n=7):
        from cometbft_tpu.types.basic import (
            BLOCK_ID_FLAG_COMMIT,
            BLOCK_ID_FLAG_NIL,
            BlockID,
            PartSetHeader,
            Timestamp,
        )
        from cometbft_tpu.types.block import Commit
        from cometbft_tpu.types.vote import CommitSig

        bid = BlockID(
            hash=hashlib.sha256(b"csb-block").digest(),
            part_set_header=PartSetHeader(
                3, hashlib.sha256(b"csb-parts").digest()
            ),
        )
        sigs = []
        for i in range(n):
            flag = BLOCK_ID_FLAG_NIL if i % 3 == 2 else BLOCK_ID_FLAG_COMMIT
            ts = (
                Timestamp(0, 0)
                if i == 4  # zero timestamp -> field omitted entirely
                else Timestamp(1_700_000_000 + i, 123_456_789 * (i % 2))
            )
            sigs.append(
                CommitSig(
                    block_id_flag=flag,
                    validator_address=bytes([i]) * 20,
                    timestamp=ts,
                    signature=bytes(64),
                )
            )
        return Commit(height=12345, round_=2, block_id=bid, signatures=sigs)

    def test_differential_all_indices(self, nlib):
        commit = self._commit()
        got = commit.all_vote_sign_bytes("csb-chain")
        want = [
            commit.vote_sign_bytes("csb-chain", i)
            for i in range(len(commit.signatures))
        ]
        assert got == want

    def test_differential_subset_and_fallback(self, nlib, monkeypatch):
        commit = self._commit()
        got = commit.all_vote_sign_bytes("csb-chain", [5, 1, 2])
        want = [commit.vote_sign_bytes("csb-chain", i) for i in (5, 1, 2)]
        assert got == want
        # python fallback path must agree too
        monkeypatch.setattr(native, "lib", lambda: None)
        assert commit.all_vote_sign_bytes("csb-chain", [5, 1, 2]) == want

    @pytest.mark.parametrize("with_native", (True, False))
    @pytest.mark.parametrize(
        "indices",
        [
            [0, 1, 2, 3],  # a light pass's head of the commit
            [1, 3, 4, 6, 7, 9],  # a strict subset, as ABSENT entries leave
            [9, 2, 8, 2, 0],  # out of order, one twice
            [2, 5, 8],  # NIL votes only: no block id in what they signed
            [4],  # one, and its timestamp is the zero time
            [],
            None,
        ],
        ids=["head", "subset", "unordered", "nil_only", "one", "none", "all"],
    )
    def test_inputs_built_by_passes_equal_the_encoder(
        self, nlib, monkeypatch, indices, with_native
    ):
        """ISSUE 31: the native call's flags, seconds and nanos come from
        C-level passes over the chosen signatures and its blob is cut by the
        offsets read out once; byte for byte ``vote_sign_bytes`` an index."""
        from cometbft_tpu.types.basic import Timestamp

        commit = self._commit(10)
        commit.signatures[1].timestamp = Timestamp(-5, 999_999_999)
        commit.signatures[3].timestamp = Timestamp((1 << 62) + 7, 1)
        if not with_native:
            monkeypatch.setattr(native, "lib", lambda: None)
        idxs = range(len(commit.signatures)) if indices is None else indices
        want = [commit.vote_sign_bytes("csb-chain", i) for i in idxs]
        got = commit.all_vote_sign_bytes("csb-chain", indices)
        assert got == want
        assert all(type(b) is bytes for b in got)

    def test_a_time_past_int64_goes_to_the_encoder(self, nlib):
        from cometbft_tpu.types.basic import Timestamp

        commit = self._commit()
        commit.signatures[2].timestamp = Timestamp(1 << 70, 0)
        try:
            want = [commit.vote_sign_bytes("csb-chain", i) for i in (1, 2)]
        except Exception as e:  # noqa: BLE001 — then both refuse alike
            with pytest.raises(type(e)):
                commit.all_vote_sign_bytes("csb-chain", [1, 2])
        else:
            assert commit.all_vote_sign_bytes("csb-chain", [1, 2]) == want


class TestBuildRace:
    """ROADMAP D12: six xdist workers on a fresh checkout all build the
    same library; each compiles to a name of its own."""

    def test_two_builds_never_share_an_output_path(self, tmp_path, monkeypatch):
        import subprocess

        so = str(tmp_path / "_lib.so")
        outs = []

        def fake_run(cmd, **kw):
            outs.append(cmd[cmd.index("-o") + 1])

        monkeypatch.setattr(subprocess, "run", fake_run)
        assert native._build("x.cpp", so) and native._build("x.cpp", so)
        assert len(set(outs)) == 2 and so + ".tmp" not in outs
        assert all(os.path.dirname(o) == str(tmp_path) for o in outs)
        assert os.listdir(tmp_path) == ["_lib.so"]  # renamed, nothing left

    def test_failed_build_leaves_nothing_and_a_fresh_library_is_taken(
        self, tmp_path, monkeypatch
    ):
        import subprocess

        src, so = tmp_path / "x.cpp", tmp_path / "_lib.so"
        src.write_text("")

        def failing_run(cmd, **kw):
            # another process finishes its build while this one fails
            so.write_bytes(b"built elsewhere")
            raise subprocess.CalledProcessError(1, cmd)

        monkeypatch.setattr(subprocess, "run", failing_run)
        assert not native._build(str(src), str(so))
        assert sorted(os.listdir(tmp_path)) == ["_lib.so", "x.cpp"]
        so.unlink()
        assert native._ready(str(src), str(so))  # the loser finds it fresh
        monkeypatch.setattr(
            subprocess, "run",
            lambda cmd, **kw: (_ for _ in ()).throw(FileNotFoundError("g++")),
        )
        so.unlink()
        assert not native._ready(str(src), str(so))  # no toolchain, no library

    def test_concurrent_real_builds_all_end_with_a_loadable_library(
        self, tmp_path
    ):
        import shutil
        import threading

        if shutil.which("g++") is None:
            pytest.skip("no g++")
        src, so = tmp_path / "one.cpp", tmp_path / "_one.so"
        src.write_text('extern "C" int one() { return 1; }\n')
        results = []
        threads = [
            threading.Thread(
                target=lambda: results.append(native._build(str(src), str(so)))
            )
            for _ in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results == [True] * 4
        assert sorted(os.listdir(tmp_path)) == ["_one.so", "one.cpp"]
        assert ctypes.CDLL(str(so)).one() == 1
