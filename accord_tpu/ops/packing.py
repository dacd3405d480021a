"""Device-side packing/compare helpers for the 128-bit timestamp format.

The host format (primitives.timestamp) packs a timestamp as
``msb = epoch<<16 | hlc_hi16``, ``lsb = hlc_lo48<<16 | flags``, plus an
int32 node id; the total order is (msb, lsb, node) compared *unsigned*
(ref: accord-core/src/main/java/accord/primitives/Timestamp.java:41-45 and
its compareTo).  On device we keep exactly that layout as three arrays
(int64, int64, int32) so TxnIds are usable directly as sort/compare keys.

JAX int64 is signed, and the lsb's top bit is live for realistic HLCs
(micros-since-epoch exceeds 2^47), so unsigned comparison is implemented by
flipping the sign bit — ``x ^ i64min`` maps unsigned order onto signed order.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

def enable_x64() -> None:
    """Opt in to 64-bit JAX for the device data plane.  Call once at process
    start, before any other JAX work (entry points, bench, and the test
    conftest all do)."""
    jax.config.update("jax_enable_x64", True)


def startup() -> str:
    """Process start-up for every entry point (burn, maelstrom, net.server,
    bench, tools/*, chip_smoke): 64-bit JAX plus a persistent compile cache
    the caller can place.  ``JAX_COMPILATION_CACHE_DIR`` set -> jax reads it
    and nothing is set here; unset -> ``<checkout>/.jax_cache``, a fixed
    path (the path is part of the cache key, so a directory that moves
    never hits).  Returns the cache directory in effect."""
    import os
    enable_x64()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir",
            os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def ensure_x64() -> None:
    """The protocol's ids are 128-bit (2 x int64 words); the device data
    plane requires 64-bit integer support.  On TPU, int64 compares/bitwise
    are emulated with int32 pairs by XLA — acceptable here (the kernels are
    compare/reduce bound, and the one matmul runs in bf16).

    x64 is a PRECONDITION, not a side effect: flipping the process-global
    flag lazily mid-run would silently change dtype-promotion semantics for
    unrelated JAX code in the host application.  Callers must opt in via
    startup() / enable_x64() (or jax.config / JAX_ENABLE_X64) at startup.
    """
    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            "accord_tpu device kernels require 64-bit JAX; call "
            "accord_tpu.ops.packing.enable_x64() (or set JAX_ENABLE_X64=true) "
            "at process start before building device state")

from ..primitives.timestamp import Timestamp, TxnId, TxnKind

_MASK64 = (1 << 64) - 1
I64_SIGN = -(1 << 63)


def to_i64(v: int) -> int:
    """Unsigned 64-bit value -> the same bits as a python int in int64 range."""
    v &= _MASK64
    return v - (1 << 64) if v >= (1 << 63) else v


def to_u64(v: int) -> int:
    """Signed int64 bits -> unsigned python int."""
    return int(v) & _MASK64


def _flip(x):
    """Map unsigned int64 order onto signed order."""
    return jnp.bitwise_xor(x, jnp.int64(I64_SIGN))


def ts_lt(a_msb, a_lsb, a_node, b_msb, b_lsb, b_node):
    """Elementwise (a < b) under the timestamp total order, unsigned on the
    two int64 words, then node id."""
    am, bm = _flip(a_msb), _flip(b_msb)
    al, bl = _flip(a_lsb), _flip(b_lsb)
    return (am < bm) | ((am == bm) & ((al < bl) | ((al == bl) & (a_node < b_node))))


def ts_le(a_msb, a_lsb, a_node, b_msb, b_lsb, b_node):
    return ~ts_lt(b_msb, b_lsb, b_node, a_msb, a_lsb, a_node)


def ts_eq(a_msb, a_lsb, a_node, b_msb, b_lsb, b_node):
    return (a_msb == b_msb) & (a_lsb == b_lsb) & (a_node == b_node)


def masked_ts_max(msb, lsb, node, mask):
    """Lexicographic max of the timestamps selected by ``mask`` along the last
    axis; returns Timestamp.NONE's bits where the mask is empty.

    Three vectorized passes (max msb, then max lsb among msb-ties, then node)
    instead of a custom reduction — compiles to plain reduces on the VPU.
    """
    neg = jnp.int64(I64_SIGN)  # unsigned-min sentinel after flip
    fm = jnp.where(mask, _flip(msb), neg)
    m1 = jnp.max(fm, axis=-1, keepdims=True)
    tie1 = mask & (fm == m1)
    fl = jnp.where(tie1, _flip(lsb), neg)
    m2 = jnp.max(fl, axis=-1, keepdims=True)
    tie2 = tie1 & (fl == m2)
    nn = jnp.where(tie2, node, jnp.int32(-1))
    m3 = jnp.max(nn, axis=-1)
    any_ = jnp.any(mask, axis=-1)
    out_msb = jnp.where(any_, _flip(m1[..., 0]), jnp.int64(0))
    out_lsb = jnp.where(any_, _flip(m2[..., 0]), jnp.int64(0))
    out_node = jnp.where(any_, m3, jnp.int32(0))
    return out_msb, out_lsb, out_node


# -- host-side packing --------------------------------------------------------

def pack_timestamps(ts_list) -> tuple:
    """[Timestamp] -> (msb int64[n], lsb int64[n], node int32[n]) numpy."""
    ensure_x64()
    n = len(ts_list)
    msb = np.zeros(n, dtype=np.int64)
    lsb = np.zeros(n, dtype=np.int64)
    node = np.zeros(n, dtype=np.int32)
    for i, t in enumerate(ts_list):
        msb[i] = to_i64(t.msb)
        lsb[i] = to_i64(t.lsb)
        node[i] = t.node
    return msb, lsb, node


def unpack_timestamp(msb: int, lsb: int, node: int) -> Timestamp:
    return Timestamp(to_u64(msb), to_u64(lsb), int(node))


def unpack_txn_id(msb: int, lsb: int, node: int) -> TxnId:
    return TxnId(to_u64(msb), to_u64(lsb), int(node))


def kind_ordinal(t: TxnId) -> int:
    return int(t.kind())


KIND_COUNT = len(TxnKind)
