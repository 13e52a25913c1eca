"""Native host-acceleration loader.

Compiles hostaccel.cpp to a shared object on first use (g++ is part of
the image toolchain; no pybind11 — plain `ctypes` over an extern "C"
ABI) and exposes numpy-friendly wrappers. Every entry point has a
pure-Python fallback, so the package works identically when no
compiler is present — `available()` says which path is live, and a
failed build is logged at warning level once.

The binary is named by the SHA-256 of the source it was built from, so
a binary left on disk by an older hostaccel.cpp (the working tree keeps
`*.so` out of git, and a copied tree carries them along) can never
load: a changed source is a new name, which does not exist until this
process builds it.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading
import time
from typing import Optional, Sequence

import numpy as np

_log = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "hostaccel.cpp")

_lock = threading.Lock()
_lib = None
_tried = False
_built_s: Optional[float] = None  # seconds, when THIS process compiled


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"_hostaccel.{digest}.so")


def _compile(so: str) -> bool:
    # Compile to a per-pid temp path and os.replace() into place:
    # concurrent processes (e.g. the multi-process e2e testnet) would
    # otherwise interleave writes into the shared .so and a reader could
    # dlopen a permanently corrupt file.
    global _built_s
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-o", tmp, _SRC]
    t0 = time.monotonic()
    try:
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        _log.warning("hostaccel compile unavailable (%s): the host "
                     "pack runs on the pure-Python path", e)
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False
    if r.returncode != 0:
        _log.warning("hostaccel compile failed; the host pack runs on "
                     "the pure-Python path:\n%s", r.stderr[-2000:])
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False
    try:
        os.replace(tmp, so)
    except OSError as e:
        _log.warning("hostaccel install failed: %s", e)
        return False
    _built_s = round(time.monotonic() - t0, 3)
    return True


def _remove_binaries(keep: Optional[str] = None) -> None:
    for path in glob.glob(os.path.join(_DIR, "_hostaccel*.so")):
        if path != keep:
            try:
                os.unlink(path)
            except OSError:
                pass


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        so = _so_path()
        if not os.path.exists(so):
            if not _compile(so):
                return None
            _remove_binaries(keep=so)  # builds of older sources
        try:
            lib = ctypes.CDLL(so)
            assert lib.hostaccel_abi_version() == 1
        except (OSError, AttributeError, AssertionError) as e:
            _log.warning("hostaccel load failed: %s", e)
            return None
        u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
        u64p = np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS")
        lib.batch_sha512.argtypes = [u8p, u64p, u64p, ctypes.c_uint64,
                                     u8p]
        lib.batch_sha512.restype = None
        lib.ed25519_batch_digest.argtypes = [u8p, u8p, u8p, u64p, u64p,
                                             ctypes.c_uint64, u8p]
        lib.ed25519_batch_digest.restype = None
        lib.ed25519_batch_challenge.argtypes = [u8p, u8p, u8p, u64p,
                                                u64p, ctypes.c_uint64,
                                                u8p]
        lib.ed25519_batch_challenge.restype = None
        lib.batch_reduce_mod_l.argtypes = [u8p, ctypes.c_uint64, u8p]
        lib.batch_reduce_mod_l.restype = None
        i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
        i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
        lib.ed25519_pack.argtypes = [u8p, u8p, u8p, u64p, u64p,
                                     ctypes.c_uint64, i32p, i32p, i32p,
                                     i32p, i32p, i32p, u8p]
        lib.ed25519_pack.restype = None
        lib.ed25519_pack_commits.argtypes = [
            u8p, u8p, u8p, u64p, u64p, u64p, u64p,
            i32p, i64p, i64p, ctypes.c_uint64,
            i32p, i32p, i32p, i32p, i32p, i32p, u8p,
        ]
        lib.ed25519_pack_commits.restype = None
        u64arr = np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS")
        lib.batch_keccak_f1600.argtypes = [u64arr, ctypes.c_uint64]
        lib.batch_keccak_f1600.restype = None
        lib.sr25519_batch_challenges.argtypes = [
            u8p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            u8p, ctypes.c_uint64, u8p, u8p, ctypes.c_uint64, u8p,
        ]
        lib.sr25519_batch_challenges.restype = None
        lib.secp256k1_pack.argtypes = [
            u8p, u8p, u8p, u64p, u64p, ctypes.c_uint64,
            i32p, i32p, i32p, i32p, i32p, i32p, u8p, u64p,
        ]
        lib.secp256k1_pack.restype = None
        lib.valset_root.argtypes = [u8p, ctypes.c_uint64, u8p, u64p,
                                    ctypes.c_uint64, u8p, u8p]
        lib.valset_root.restype = None
        # the differential tests' surfaces (tests/test_native.py)
        lib.batch_sha256.argtypes = [u8p, u64p, u64p, ctypes.c_uint64, u8p]
        lib.batch_sha256.restype = None
        lib.secp256k1_batch_mulmod_n.argtypes = [u8p, u8p, ctypes.c_uint64,
                                                 u8p]
        lib.secp256k1_batch_mulmod_n.restype = None
        lib.secp256k1_batch_invmod_n.argtypes = [u8p, ctypes.c_uint64, u8p]
        lib.secp256k1_batch_invmod_n.restype = None
        _lib = lib
        return _lib


def rebuild() -> dict:
    """Throw away every built binary and compile hostaccel.cpp again
    now (chip_smoke.py: the library a chip run loads was built in that
    run, from the source git tracks). Must run before first use."""
    global _tried
    with _lock:
        if _lib is not None:
            raise RuntimeError("hostaccel already loaded in this process")
        _remove_binaries()
        _tried = False
    return build_info()


def build_info() -> dict:
    """Which path is live: the binary's path (named by source hash) and
    the seconds this process spent compiling it, None if it was found
    on disk."""
    ok = _load() is not None
    return {"available": ok,
            "binary": os.path.basename(_so_path()) if ok else None,
            "built_s": _built_s}


def available() -> bool:
    return _load() is not None


def batch_sha512(rows: Sequence[bytes]) -> np.ndarray:
    """SHA-512 of each row; returns (n, 64) uint8. One native call for
    the whole batch (vs n hashlib calls)."""
    n = len(rows)
    out = np.empty((n, 64), np.uint8)
    lib = _load()
    if lib is None:
        for i, r in enumerate(rows):
            out[i] = np.frombuffer(hashlib.sha512(r).digest(), np.uint8)
        return out
    data = np.frombuffer(b"".join(rows), np.uint8)
    if data.size == 0:
        data = np.zeros(1, np.uint8)  # valid pointer for all-empty rows
    lens = np.asarray([len(r) for r in rows], np.uint64)
    offs = np.zeros(n, np.uint64)
    if n > 1:
        np.cumsum(lens[:-1], out=offs[1:])
    lib.batch_sha512(np.ascontiguousarray(data), offs, lens, n, out)
    return out


def _msg_arrays(msgs: Sequence[bytes]):
    n = len(msgs)
    mdata = np.frombuffer(b"".join(msgs), np.uint8)
    if mdata.size == 0:
        mdata = np.zeros(1, np.uint8)  # valid pointer for empty msgs
    mlens = np.asarray([len(m) for m in msgs], np.uint64)
    moffs = np.zeros(n, np.uint64)
    if n > 1:
        np.cumsum(mlens[:-1], out=moffs[1:])
    return np.ascontiguousarray(mdata), moffs, mlens


def ed25519_batch_digest(r_raw: np.ndarray, a_raw: np.ndarray,
                         msgs: Sequence[bytes]) -> np.ndarray:
    """Digests SHA512(R_i || A_i || M_i) for the ed25519 verify batch
    without materializing the concatenation in Python."""
    n = len(msgs)
    out = np.empty((n, 64), np.uint8)
    lib = _load()
    if lib is None:
        sha512 = hashlib.sha512
        rb, ab = r_raw.tobytes(), a_raw.tobytes()
        for i, m in enumerate(msgs):
            d = sha512(rb[32 * i:32 * i + 32]
                       + ab[32 * i:32 * i + 32] + m).digest()
            out[i] = np.frombuffer(d, np.uint8)
        return out
    mdata, moffs, mlens = _msg_arrays(msgs)
    lib.ed25519_batch_digest(
        np.ascontiguousarray(r_raw[:n].reshape(n, 32)),
        np.ascontiguousarray(a_raw[:n].reshape(n, 32)),
        mdata, moffs, mlens, n, out,
    )
    return out


_L = 2**252 + 27742317777372353535851937790883648493


def ed25519_batch_challenge(r_raw: np.ndarray, a_raw: np.ndarray,
                            msgs: Sequence[bytes]) -> Optional[np.ndarray]:
    """h_i = SHA512(R_i || A_i || M_i) mod L as (n, 32) LE bytes — the
    fused digest+reduce staging pass. None when no native library (the
    caller keeps its hashlib+bigint fallback)."""
    lib = _load()
    if lib is None:
        return None
    n = len(msgs)
    out = np.empty((n, 32), np.uint8)
    mdata, moffs, mlens = _msg_arrays(msgs)
    lib.ed25519_batch_challenge(
        np.ascontiguousarray(r_raw[:n].reshape(n, 32)),
        np.ascontiguousarray(a_raw[:n].reshape(n, 32)),
        mdata, moffs, mlens, n, out,
    )
    return out


def ed25519_pack(pub_cat: bytes, sig_cat: bytes,
                 msgs: Sequence[bytes], padded: int):
    """Full host pack: (n-concatenated pubkeys, sigs, msgs) -> device
    arrays padded to `padded` rows. None without the native library.

    Returns (ay, asign, ry, rsign, sdig, hdig, precheck) matching
    ops/ed25519_kernel.pack_batch's fast path exactly (differential
    test: tests/test_native.py pack parity)."""
    lib = _load()
    if lib is None:
        return None
    n = len(msgs)
    ay = np.zeros((padded, 20), np.int32)
    ry = np.zeros((padded, 20), np.int32)
    asign = np.zeros(padded, np.int32)
    rsign = np.zeros(padded, np.int32)
    sdig = np.zeros((padded, 64), np.int32)
    hdig = np.zeros((padded, 64), np.int32)
    precheck = np.zeros(padded, np.uint8)
    if n:
        mdata, moffs, mlens = _msg_arrays(msgs)
        pubs = np.frombuffer(pub_cat, np.uint8)
        sigs = np.frombuffer(sig_cat, np.uint8)
        lib.ed25519_pack(
            np.ascontiguousarray(pubs), np.ascontiguousarray(sigs),
            mdata, moffs, mlens, n,
            ay, asign, ry, rsign, sdig, hdig, precheck,
        )
    return ay, asign, ry, rsign, sdig, hdig, precheck.astype(np.bool_)


def ed25519_pack_commits(pub_cat: bytes, sig_cat: bytes,
                         templates, row_tmpl: np.ndarray,
                         row_secs: np.ndarray, row_nanos: np.ndarray,
                         padded: int):
    """Fused streamed-chunk pack: canonical sign-bytes are built
    in-native from (per-commit template, per-row timestamp) — no Python
    message list at all. `templates` is [(pre_bytes, suf_bytes)];
    row_tmpl[i] indexes it. Returns the same tuple as ed25519_pack, or
    None without the native library."""
    lib = _load()
    if lib is None:
        return None
    n = len(row_tmpl)
    ay = np.zeros((padded, 20), np.int32)
    ry = np.zeros((padded, 20), np.int32)
    asign = np.zeros(padded, np.int32)
    rsign = np.zeros(padded, np.int32)
    sdig = np.zeros((padded, 64), np.int32)
    hdig = np.zeros((padded, 64), np.int32)
    precheck = np.zeros(padded, np.uint8)
    if n:
        chunks, pre_off, pre_len, suf_off, suf_len = [], [], [], [], []
        pos = 0
        for pre, suf in templates:
            pre_off.append(pos)
            pre_len.append(len(pre))
            pos += len(pre)
            suf_off.append(pos)
            suf_len.append(len(suf))
            pos += len(suf)
            chunks.append(pre)
            chunks.append(suf)
        tmpl = np.frombuffer(b"".join(chunks), np.uint8)
        if tmpl.size == 0:
            tmpl = np.zeros(1, np.uint8)
        lib.ed25519_pack_commits(
            np.ascontiguousarray(np.frombuffer(pub_cat, np.uint8)),
            np.ascontiguousarray(np.frombuffer(sig_cat, np.uint8)),
            np.ascontiguousarray(tmpl),
            np.asarray(pre_off, np.uint64), np.asarray(pre_len, np.uint64),
            np.asarray(suf_off, np.uint64), np.asarray(suf_len, np.uint64),
            np.ascontiguousarray(row_tmpl, dtype=np.int32),
            np.ascontiguousarray(row_secs, dtype=np.int64),
            np.ascontiguousarray(row_nanos, dtype=np.int64),
            n, ay, asign, ry, rsign, sdig, hdig, precheck,
        )
    return ay, asign, ry, rsign, sdig, hdig, precheck.astype(np.bool_)


def batch_keccak_f1600(states: np.ndarray) -> Optional[np.ndarray]:
    """Batched keccak permutation: (n, 25) uint64 lanes -> permuted
    copy; None without the native library (callers keep the numpy
    route)."""
    lib = _load()
    if lib is None:
        return None
    out = np.ascontiguousarray(states, dtype=np.uint64).copy()
    lib.batch_keccak_f1600(out, out.shape[0])
    return out


def batch_reduce_mod_l(digests: np.ndarray) -> Optional[np.ndarray]:
    """(n, 64) LE digests -> (n, 32) LE scalars mod L; None without the
    native library."""
    lib = _load()
    if lib is None:
        return None
    n = digests.shape[0]
    out = np.empty((n, 32), np.uint8)
    lib.batch_reduce_mod_l(
        np.ascontiguousarray(digests.reshape(n, 64)), n, out
    )
    return out


def sr25519_batch_challenges(prefix_state: bytes, pos: int,
                             pos_begin: int, cur_flags: int,
                             msgs: np.ndarray, pks: np.ndarray,
                             rs: np.ndarray) -> Optional[np.ndarray]:
    """Whole sr25519 merlin challenge transcripts in one native call:
    (n, L) msgs + (n, 32) pks + (n, 32) R encodings -> (n, 64) raw
    challenge bytes. None without the native library (callers keep the
    numpy BatchStrobe route — the differential reference,
    tests/test_native.py)."""
    lib = _load()
    if lib is None:
        return None
    n = msgs.shape[0]
    out = np.empty((n, 64), np.uint8)
    lib.sr25519_batch_challenges(
        np.frombuffer(prefix_state, np.uint8), pos, pos_begin,
        cur_flags, np.ascontiguousarray(msgs, np.uint8),
        msgs.shape[1], np.ascontiguousarray(pks, np.uint8),
        np.ascontiguousarray(rs, np.uint8), n, out,
    )
    return out


def secp256k1_pack(pub_cat: bytes, sig_cat: bytes, msgs, padded: int):
    """Full host pack of one ECDSA chunk: n concatenated 33-byte keys
    and 64-byte signatures, and their messages as a list of bytes or as
    rows of one matrix (anything with `mat` and `lens`, as
    types/canonical.SignRows: each row is hashed where it lies) ->
    device arrays padded to `padded` rows. None without the native
    library.

    Returns (qx, qparity, u1dig, u2dig, xr1, xr2, precheck), equal
    array for array to the Python loop of ops/ecdsa_kernel.pack_batch
    (tests/test_native.py)."""
    lib = _load()
    if lib is None:
        return None
    n = len(msgs)
    if len(pub_cat) != 33 * n or len(sig_cat) != 64 * n or padded < n:
        raise ValueError("secp256k1_pack: keys, signatures and messages "
                         "of unequal number, or more rows than `padded`")
    qx = np.zeros((padded, 20), np.int32)
    xr1 = np.zeros((padded, 20), np.int32)
    xr2 = np.zeros((padded, 20), np.int32)
    qparity = np.zeros(padded, np.int32)
    u1dig = np.zeros((padded, 64), np.int32)
    u2dig = np.zeros((padded, 64), np.int32)
    precheck = np.zeros(padded, np.uint8)
    if n:
        if hasattr(msgs, "mat"):
            mat = np.ascontiguousarray(msgs.mat, dtype=np.uint8)
            mlens = np.ascontiguousarray(msgs.lens, dtype=np.uint64)
            if int(mlens.max()) > mat.shape[1]:
                raise ValueError("secp256k1_pack: a row longer than "
                                 "the matrix is wide")
            moffs = np.arange(n, dtype=np.uint64) * np.uint64(mat.shape[1])
            mdata = mat.reshape(-1)
            if mdata.size == 0:
                mdata = np.zeros(1, np.uint8)
        else:
            mdata, moffs, mlens = _msg_arrays(msgs)
        lib.secp256k1_pack(
            np.frombuffer(pub_cat, np.uint8), np.frombuffer(sig_cat, np.uint8),
            mdata, moffs, mlens, n,
            qx, qparity, u1dig, u2dig, xr1, xr2, precheck,
            np.empty((n, 8), np.uint64),
        )
    return qx, qparity, u1dig, u2dig, xr1, xr2, precheck.astype(np.bool_)


def valset_root(keys: bytes, klen: int, fields: np.ndarray,
                powers: np.ndarray) -> Optional[bytes]:
    """The merkle root of a validator set's SimpleValidator leaves
    (types/validator.ValidatorSet.hash) in ONE native call: n
    concatenated keys of `klen` bytes each, each key's PublicKey field
    number (Validator.bytes()) and voting power (int64). The leaves are
    written, hashed and folded into crypto/merkle.hash_from_byte_slices's
    tree in C; n >= 1. None without the native library."""
    lib = _load()
    if lib is None:
        return None
    n = len(powers)
    if (not n or not 0 < klen <= 128 or len(keys) != klen * n
            or len(fields) != n):
        raise ValueError("valset_root: no validator, keys, fields and "
                         "powers of unequal number, or a key length "
                         "outside 1..128")
    out = np.empty(32, np.uint8)
    lib.valset_root(
        np.frombuffer(keys, np.uint8), klen,
        np.ascontiguousarray(fields, dtype=np.uint8),
        np.ascontiguousarray(powers, dtype=np.int64).view(np.uint64), n,
        np.empty((n, 32), np.uint8), out)
    return out.tobytes()
