"""AOT-compile the main-path device programs for a TPU v5e at REAL widths.

No chip is attached to the test box: the TPU compiler is installed and
compiles for a DESCRIBED ``v5e:2x2`` topology, so what the chip's compiler
would refuse (or balloon past 16 GB) fails here at no chip time.  Nothing
runs — these say nothing about results or speed; ``chip_smoke.py`` is the
chip run.  Shapes are the store phase of ``chip_smoke.py`` (capacity
131072 = 100k in-flight rounded to the mirror's pow2, 8 intervals, B=2048)
with the s/k budgets that phase learned on the chip (CHANGES.md, PR 21).

The topology is described inside a module fixture (never at import, in a
skipif or in parametrize): only the xdist worker that is handed this file
loads the TPU library.  The file sorts first so a suite cut at its time
limit still ran it.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from accord_tpu.ops import deps_kernel as dk
from accord_tpu.ops import drain_kernel as drk

N, M, B = 131072, 8, 2048          # slots, intervals/txn, queries/batch
S_FLAT, K_ROW = 180224, 512        # learned entry budget / widest row
HBM_BYTES = 16 * 10**9             # one v5e chip


@pytest.fixture(scope="module")
def topo():
    import os
    import signal
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    # loading the TPU library installs a SIGTERM handler that prints a stack
    # trace; a suite cut at its time limit TERMs every worker, and that
    # trace lands on pytest's progress line.  Keep this worker's own handler.
    on_term = signal.getsignal(signal.SIGTERM)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        signal.signal(signal.SIGTERM, on_term)


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-device compile can be written to the persistent cache
    but never read back without a chip (guide on-chip-measurement §2)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", saved)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _table(n, m, sh1, sh2):
    i64, i32 = jnp.int64, jnp.int32
    return dk.DepsTable(_sds((n,), i64, sh1), _sds((n,), i64, sh1),
                        _sds((n,), i32, sh1), _sds((n,), i32, sh1),
                        _sds((n,), i32, sh1), _sds((n, m), i64, sh2),
                        _sds((n, m), i64, sh2))


def _attr_cols(n, sh):
    i64, i32 = jnp.int64, jnp.int32
    return dk.AttrCols(_sds((n,), i32, sh), _sds((n,), i32, sh),
                       _sds((n,), i64, sh), _sds((n,), i64, sh),
                       _sds((n,), i32, sh), _sds((n,), i64, sh),
                       _sds((n,), i64, sh), _sds((n,), i32, sh),
                       _sds((n,), jnp.bool_, sh))


def _attr_index(sh, f=16, t=1024, l=4096):
    i64, i32 = jnp.int64, jnp.int32
    return dk.AttrIndex(_sds((f,), i64, sh), _sds((f + 1,), i64, sh),
                        _sds((f + 1,), i64, sh), _sds((f + 1,), i32, sh),
                        _sds((t,), i64, sh), _sds((t + 1,), i32, sh),
                        _sds((l,), i64, sh), _sds((l,), i64, sh),
                        _sds((l,), i64, sh), _sds((l,), i32, sh),
                        _sds((), i64, sh))


def _query_args(sh, extra_cols=0):
    i64, i32 = jnp.int64, jnp.int32
    return (_sds((B, 7 + 2 * M + extra_cols), i64, sh), _sds((B,), i64, sh),
            _sds((), i64, sh), _sds((), i64, sh), _sds((), i32, sh))


def _fits(compiled):
    ma = compiled.memory_analysis()
    used = ma.temp_size_in_bytes + ma.argument_size_in_bytes
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB does not fit one v5e chip"


def test_dense_attributed_scan_compiles(topo, no_persistent_cache):
    one = SingleDeviceSharding(topo.devices[0])
    qmat, rankb, pm, pl, pn = _query_args(one)
    compiled = dk.calculate_deps_flat_attr.lower(
        _table(N, M, one, one), _attr_cols(N, one), _attr_index(one),
        qmat, rankb, pm, pl, pn, m=M, s=S_FLAT, k=K_ROW,
        wide=dk.wide_codes(N, M, M), floors=True, elide=True).compile()
    _fits(compiled)


def test_bucketed_attributed_scan_compiles(topo, no_persistent_cache):
    one = SingleDeviceSharding(topo.devices[0])
    g, k_b, w, span, keff = 16384, 128, 16, 4, 128
    i64, i32 = jnp.int64, jnp.int32
    buckets = dk.BucketTable(
        *(_sds((g, k_b), dt, one)
          for dt in (i64, i64, i32, i32, i64, i64, i32, i32)),
        *(_sds((w,), dt, one)
          for dt in (i64, i64, i32, i32, i64, i64, i32, i32)))
    qmat, rankb, pm, pl, pn = _query_args(one, extra_cols=M * span)
    compiled = dk.bucketed_attr_jit.lower(
        _table(N, M, one, one), _attr_cols(N, one), _attr_index(one),
        buckets, qmat, rankb, M, span, S_FLAT, K_ROW, (pm, pl, pn),
        keff=keff, wide=dk.wide_codes(N, M, M), floors=True,
        elide=True).compile()
    _fits(compiled)


@pytest.mark.parametrize("rows,cells", [(128, 2048), (4096, 32768)])
def test_table_sync_compiles(topo, no_persistent_cache, rows, cells):
    """The one table-sync program at the store cells' sizes: 131,072 slots
    of 8 intervals, 15,625 buckets in 16,384 rows.  A 64-txn flush leaves
    128 dirty rows and pads its cells to the 2,048 floor; a 2,048-txn flush
    4,096 rows and 32,768 cells."""
    from accord_tpu.local.device_index import (_BUCKET_REC, _CELL_WORDS,
                                               _sync_tables)
    one = SingleDeviceSharding(topo.devices[0])
    # idx, msb, lsb, node, status; kind, lo[M], hi[M]; dom, emsb, elsb,
    # enode, eknown; then a cell's index and its record
    words = rows * (5 + 1 + 2 * M + 5) + cells * (1 + _CELL_WORDS)
    compiled = _sync_tables.lower(
        _table(N, M, one, one), _attr_cols(N, one),
        tuple(_sds((16384, 128), _BUCKET_REC[f], one)
              for f in _BUCKET_REC.names),
        _sds((words,), jnp.int64, one), n_rows=rows,
        n_cells=cells).compile()
    _fits(compiled)


def _ell_state(n, d, sh):
    i64, i32 = jnp.int64, jnp.int32
    return drk.EllDrainState(_sds((n, d), i32, sh), _sds((n,), i32, sh),
                             _sds((n,), i64, sh), _sds((n,), i64, sh),
                             _sds((n,), i32, sh), _sds((n,), jnp.bool_, sh))


def test_ell_logdepth_drain_compiles(topo, no_persistent_cache):
    one = SingleDeviceSharding(topo.devices[0])
    compiled = drk._drain_ell_logdepth_full.lower(
        _ell_state(N, 8, one)).compile()
    _fits(compiled)


def test_dense_logdepth_drain_compiles(topo, no_persistent_cache):
    one = SingleDeviceSharding(topo.devices[0])
    n = 4096
    i64, i32 = jnp.int64, jnp.int32
    state = drk.DrainState(_sds((n, n), jnp.bool_, one), _sds((n,), i32, one),
                           _sds((n,), i64, one), _sds((n,), i64, one),
                           _sds((n,), i32, one), _sds((n,), jnp.bool_, one))
    _fits(drk.drain_dense_logsq.lower(state).compile())
    _fits(drk._drain_dense_logdepth_full.lower(state).compile())


def test_sharded_attributed_scan_compiles_with_collective(
        topo, no_persistent_cache):
    from accord_tpu.parallel.sharded import STORE_AXIS, sharded_flat_attr
    mesh = Mesh(np.array(topo.devices[:4]), (STORE_AXIS,))
    sh1 = NamedSharding(mesh, P(STORE_AXIS))
    sh2 = NamedSharding(mesh, P(STORE_AXIS, None))
    rep = NamedSharding(mesh, P())
    # s = the mirror's initial entry budget (what the first flush launches):
    # this program's compile time grows with s (~18 s here, ~50 s at 65536)
    fn = sharded_flat_attr(mesh, M, 4096, K_ROW, dk.wide_codes(N, M, M),
                           True, True)
    compiled = fn.lower(_table(N, M, sh1, sh2), _attr_cols(N, sh1),
                        _attr_index(rep), *_query_args(rep)).compile()
    _fits(compiled)
    assert "all-gather" in compiled.as_text(), \
        "the cross-shard deps merge compiled without its collective"
