"""Native (C++) runtime components with build-on-demand + ctypes bindings.

The library is compiled from ``csrc/cometbft_native.cpp`` on first use and
cached next to the source; every consumer degrades gracefully to its pure
Python path when the toolchain or the build is unavailable.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import tempfile
import threading
from typing import Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "csrc", "cometbft_native.cpp")
_SO = os.path.join(_HERE, "_cometbft_native.so")
_BLS_SRC = os.path.join(_HERE, "csrc", "bls12381.cpp")
_BLS_SO = os.path.join(_HERE, "_cometbft_bls.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_bls_lib_handle: Optional[ctypes.CDLL] = None
_bls_tried = False


def _fresh(so: str, src: str) -> bool:
    """True when the built library can be used as-is.  A missing source
    next to an existing .so (e.g. a packaged build) counts as fresh."""
    if not os.path.exists(so):
        return False
    try:
        return os.path.getmtime(so) >= os.path.getmtime(src)
    except OSError:
        return True


def _build(src: str, so: str) -> bool:
    """Compile to a temporary name of this call's own beside the target,
    then rename over it: several processes building at once (the test
    suite's workers on a fresh checkout) each finish a whole library, and
    the last rename wins."""
    try:
        fd, tmp = tempfile.mkstemp(
            prefix=os.path.basename(so) + ".", suffix=".tmp",
            dir=os.path.dirname(so),
        )
        os.close(fd)
    except OSError:
        return False
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", tmp, src],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.chmod(tmp, 0o755)  # mkstemp's 0600 may survive the linker
        os.replace(tmp, so)
        return True
    except (subprocess.SubprocessError, OSError):
        try:
            os.remove(tmp)
        except OSError:
            pass
        return False


def _ready(src: str, so: str) -> bool:
    """Fresh already, built now, or, where this build failed, built
    meanwhile by another process."""
    return _fresh(so, src) or _build(src, so) or _fresh(so, src)


def lib() -> Optional[ctypes.CDLL]:
    """The native library, building it if needed; None when unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("COMETBFT_TPU_NO_NATIVE"):
            return None
        if not _ready(_SRC, _SO):
            return None
        try:
            cdll = ctypes.CDLL(_SO)
        except OSError:
            return None
        # signatures
        cdll.wal_open.restype = ctypes.c_void_p
        cdll.wal_open.argtypes = [ctypes.c_char_p]
        cdll.wal_append.restype = ctypes.c_int
        cdll.wal_append.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int,
            ctypes.c_char_p,
            ctypes.c_int64,
            ctypes.c_int,
        ]
        cdll.wal_sync.restype = ctypes.c_int
        cdll.wal_sync.argtypes = [ctypes.c_void_p]
        cdll.wal_size.restype = ctypes.c_int64
        cdll.wal_size.argtypes = [ctypes.c_void_p]
        cdll.wal_close.restype = None
        cdll.wal_close.argtypes = [ctypes.c_void_p]
        cdll.ed25519_pack.restype = ctypes.c_int
        cdll.ed25519_pack.argtypes = [
            ctypes.c_char_p,  # pubs
            ctypes.c_char_p,  # sigs
            ctypes.c_char_p,  # msgs
            ctypes.POINTER(ctypes.c_int64),  # offsets
            ctypes.c_int64,  # n
            ctypes.c_char_p,  # s_out
            ctypes.c_char_p,  # m_out
            ctypes.c_char_p,  # s_ok_out
        ]
        cdll.sha512.restype = None
        cdll.sha512.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p]
        try:
            # newer symbols: a prebuilt .so from before they existed must
            # still serve the WAL paths (callers getattr-check, and
            # ``prepare_batch`` then packs in Python).  Array arguments
            # are addresses (``ops/verify._address``), None for no index.
            vp = ctypes.c_void_p
            pack_into = [
                ctypes.c_char_p,  # pubs
                ctypes.c_char_p,  # sigs
                ctypes.c_char_p,  # msgs
                vp,  # message lengths, int64 [n]
                ctypes.c_int64,  # n
                vp,  # idx, int64 [n], or None: row i
                ctypes.c_int64,  # rows of each table
                vp, vp, vp, vp, vp,  # a, r, s, m [rows, 32]; s_ok [rows]
            ]
            cdll.ed25519_pack_into.restype = ctypes.c_int
            cdll.ed25519_pack_into.argtypes = pack_into
            cdll.ed25519_pack_into_lanes.restype = ctypes.c_int
            cdll.ed25519_pack_into_lanes.argtypes = pack_into + [ctypes.c_int]
            cdll.ed25519_mod_l.restype = None
            cdll.ed25519_mod_l.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
        except AttributeError:
            pass
        try:
            cdll.commit_sign_bytes.restype = ctypes.c_int64
            cdll.commit_sign_bytes.argtypes = [
                ctypes.c_char_p, ctypes.c_int64,   # chain_id
                ctypes.c_int64, ctypes.c_int64,    # height, round
                ctypes.c_char_p, ctypes.c_int64,   # block id hash
                ctypes.c_int64,                    # psh total
                ctypes.c_char_p, ctypes.c_int64,   # psh hash
                ctypes.c_char_p,                   # flags (n bytes)
                ctypes.POINTER(ctypes.c_int64),    # ts seconds
                ctypes.POINTER(ctypes.c_int64),    # ts nanos
                ctypes.c_int64,                    # n
                ctypes.c_char_p, ctypes.c_int64,   # out, cap
                ctypes.POINTER(ctypes.c_int64),    # out offsets (n+1)
            ]
        except AttributeError:
            pass
        try:
            # the validator set's root (``proofserve/plane.valset_root``,
            # which takes the Python path where a prebuilt .so lacks it)
            vp = ctypes.c_void_p
            root = [
                ctypes.c_char_p,  # keys, n x 32 bytes
                vp,  # voting powers, int64 [n]
                ctypes.c_int64,  # n
                ctypes.c_char_p,  # the root, 32 bytes
            ]
            cdll.valset_root_ed25519.restype = ctypes.c_int
            cdll.valset_root_ed25519.argtypes = root
            cdll.valset_root_ed25519_ni.restype = ctypes.c_int
            cdll.valset_root_ed25519_ni.argtypes = root + [ctypes.c_int]
            cdll.sha256_ni.restype = ctypes.c_int
            cdll.sha256_ni.argtypes = [
                ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p, ctypes.c_int,
            ]
        except AttributeError:
            pass
        try:
            # the signature cache (``crypto/sigcache``, which keeps its
            # Python key and OrderedDict store where a prebuilt .so lacks
            # these): keys are n x 32 bytes, verdicts n bytes.  Bound on a
            # PyDLL handle of the same library, so that the GIL stays held:
            # a call of microseconds that let it go would hand it to any
            # busy thread (a receive loop decoding blocks), and the caller
            # would then wait out that thread's turn.
            vp, i64, buf = ctypes.c_void_p, ctypes.c_int64, ctypes.c_char_p
            held = ctypes.PyDLL(_SO)
            for name, restype, argtypes in (
                ("sigcache_keys", ctypes.c_int, [
                    buf, vp, i64,  # pubs, their lengths (None: all pub_len)
                    buf, vp,  # msgs, their lengths
                    buf, vp, i64,  # sigs, their lengths (None: all sig_len)
                    i64,  # n
                    buf,  # the keys out
                    ctypes.c_int,  # the block function: -1 the best
                ]),
                ("sigcache_new", vp, [i64]),
                ("sigcache_free", None, [vp]),
                ("sigcache_get_many", ctypes.c_int, [vp, buf, i64, buf]),
                ("sigcache_put_many", ctypes.c_int, [vp, buf, buf, i64]),
                ("sigcache_len", i64, [vp]),
                ("sigcache_clear", None, [vp]),
                ("sigcache_counts", None, [vp, vp]),
                ("sigcache_items", i64, [vp, buf, buf, i64]),
            ):
                fn = getattr(held, name)
                fn.restype, fn.argtypes = restype, argtypes
                setattr(cdll, name, fn)
        except (AttributeError, OSError):
            pass
        _lib = cdll
        return _lib


def bls() -> Optional[ctypes.CDLL]:
    """The BLS12-381 pairing library (the blst analog, SURVEY §2.1.1),
    building it on first use; None when the toolchain, the build, or the
    library's own pairing self-check (``bls_init``) is unavailable."""
    global _bls_lib_handle, _bls_tried
    with _lock:
        if _bls_lib_handle is not None or _bls_tried:
            return _bls_lib_handle
        _bls_tried = True
        if os.environ.get("COMETBFT_TPU_NO_NATIVE"):
            return None
        if not _ready(_BLS_SRC, _BLS_SO):
            return None
        try:
            cdll = ctypes.CDLL(_BLS_SO)
        except OSError:
            return None
        c = ctypes
        cdll.bls_init.restype = c.c_int
        cdll.bls_pubkey_from_sk.restype = c.c_int
        cdll.bls_pubkey_from_sk.argtypes = [c.c_char_p, c.c_char_p]
        cdll.bls_pubkey_validate.restype = c.c_int
        cdll.bls_pubkey_validate.argtypes = [c.c_char_p, c.c_int64]
        cdll.bls_sign.restype = c.c_int
        cdll.bls_sign.argtypes = [c.c_char_p, c.c_char_p, c.c_int64, c.c_char_p]
        cdll.bls_verify.restype = c.c_int
        cdll.bls_verify.argtypes = [
            c.c_char_p, c.c_int64, c.c_char_p, c.c_int64, c.c_char_p,
        ]
        cdll.bls_aggregate_sigs.restype = c.c_int
        cdll.bls_aggregate_sigs.argtypes = [c.c_char_p, c.c_int64, c.c_char_p]
        cdll.bls_aggregate_verify.restype = c.c_int
        cdll.bls_aggregate_verify.argtypes = [
            c.c_char_p, c.c_char_p, c.POINTER(c.c_int64), c.c_int64, c.c_char_p,
        ]
        cdll.bls_hash_to_g2.restype = c.c_int
        cdll.bls_hash_to_g2.argtypes = [c.c_char_p, c.c_int64, c.c_char_p]
        cdll.bls_sig_validate.restype = c.c_int
        cdll.bls_sig_validate.argtypes = [c.c_char_p]
        cdll.bls_g1_scalar_mul.restype = c.c_int
        cdll.bls_g1_scalar_mul.argtypes = [
            c.c_char_p, c.c_char_p, c.c_int64, c.c_char_p,
        ]
        cdll.bls_g2_scalar_mul_compressed.restype = c.c_int
        cdll.bls_g2_scalar_mul_compressed.argtypes = [
            c.c_char_p, c.c_char_p, c.c_int64, c.c_char_p,
        ]
        cdll.bls_pairing_product_is_one_serialized.restype = c.c_int
        cdll.bls_pairing_product_is_one_serialized.argtypes = [
            c.c_char_p, c.c_char_p, c.c_int64,
        ]
        # the library refuses to serve if its constants or pairing are
        # inconsistent (bilinearity/non-degeneracy/inversion self-checks)
        if cdll.bls_init() != 0:
            return None
        _bls_lib_handle = cdll
        return _bls_lib_handle
