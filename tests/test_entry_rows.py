"""The attributed kernels DERIVE each compacted entry's query row from the
rows' ends (``deps_kernel._entry_rows``, PR 34): one B-point scatter-add
and a prefix sum where ``_attr_post`` and the mesh merge binary-searched
``row_end`` for every cell of the entry buffer.  The closed form has to
equal the search in every cell — the dead tail beyond ``total`` included,
since ``pairkey`` and ``drops`` are computed there before ``live`` masks
them — and the search must not come back into the compiled programs
unnoticed."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accord_tpu.ops import deps_kernel as dk

_WHILE = re.compile(r"\bwhile\(")       # the HLO opcode, not a metadata word


def _ends(counts):
    return np.cumsum(np.asarray(counts, np.int64))


def _random_counts(b, total, n_empty, seed):
    """``b`` row counts summing to ``total`` with ``n_empty`` rows empty."""
    rng = np.random.default_rng(seed)
    counts = np.zeros(b, np.int64)
    filled = rng.choice(b, b - n_empty, replace=False)
    cuts = np.sort(rng.integers(0, total + 1, len(filled) - 1))
    counts[filled] = np.diff(np.concatenate([[0], cuts, [total]]))
    return counts


CASES = {
    # name: (row counts, s)
    "all_rows_empty": ([0] * 16, 64),
    "empty_rows_at_the_front": ([0, 0, 0, 5, 7, 1, 9], 64),
    "empty_rows_in_the_middle": ([4, 0, 0, 6, 0, 3, 11], 64),
    "empty_rows_at_the_end": ([8, 2, 13, 0, 0, 0], 64),
    "every_other_row_empty": ([3, 0] * 8, 64),
    "total_equals_s": ([16, 0, 24, 8, 16], 64),
    "total_equals_s_last_rows_empty": ([40, 24, 0, 0], 64),
    "row_end_above_s": ([30, 20, 0, 40, 25], 64),
    "first_row_alone_above_s": ([100, 3, 0, 2], 64),
    "one_row": ([37], 64),
    "one_row_empty": ([0], 64),
    "one_row_above_s": ([65], 64),
    "single_cell_buffer": ([0, 1, 0], 1),
    "b2048_with_200_empty_rows":
        (_random_counts(2048, 150_000, 200, seed=1), 163_840),
    "b2048_total_equals_s":
        (_random_counts(2048, 163_840, 200, seed=2), 163_840),
    "b2048_overflowed": (_random_counts(2048, 170_000, 64, seed=3), 163_840),
    "b64_served_flush": (_random_counts(64, 9_000, 11, seed=4), 16_384),
}


@pytest.mark.parametrize("dtype", [jnp.int64, jnp.int32],
                         ids=["int64", "int32"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_entry_rows_equals_the_search(case, dtype):
    counts, s = CASES[case]
    row_end = jnp.asarray(_ends(counts), dtype)
    want = jnp.searchsorted(row_end, jnp.arange(s, dtype=dtype),
                            side="right")
    got = jax.jit(dk._entry_rows, static_argnums=1)(row_end, s)
    assert got.shape == (s,) and got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_entry_rows_under_vmap_as_the_mesh_merge_calls_it():
    """parallel/sharded._merge_shard_blocks maps the helper over the
    shards' row ends: each shard's rows are its own."""
    s = 64
    row_end = jnp.asarray(np.stack([_ends(CASES[c][0][:4]) for c in (
        "empty_rows_in_the_middle", "total_equals_s_last_rows_empty",
        "first_row_alone_above_s", "empty_rows_at_the_end")]))
    pos = jnp.arange(s, dtype=jnp.int64)
    want = jax.vmap(lambda re: jnp.searchsorted(re, pos, side="right"))(
        row_end)
    got = jax.jit(jax.vmap(lambda re: dk._entry_rows(re, s)))(row_end)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- the search stays out of the compiled programs ---------------------------

N, M, B, S, K = 1024, 8, 64, 4096, 64


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _program_args():
    i64, i32 = jnp.int64, jnp.int32
    table = dk.DepsTable(_sds((N,), i64), _sds((N,), i64), _sds((N,), i32),
                         _sds((N,), i32), _sds((N,), i32),
                         _sds((N, M), i64), _sds((N, M), i64))
    attr = dk.AttrCols(_sds((N,), i32), _sds((N,), i32), _sds((N,), i64),
                       _sds((N,), i64), _sds((N,), i32), _sds((N,), i64),
                       _sds((N,), i64), _sds((N,), i32),
                       _sds((N,), jnp.bool_))
    f, t, l = 8, 64, 256
    aidx = dk.AttrIndex(_sds((f,), i64), _sds((f + 1,), i64),
                        _sds((f + 1,), i64), _sds((f + 1,), i32),
                        _sds((t,), i64), _sds((t + 1,), i32),
                        _sds((l,), i64), _sds((l,), i64), _sds((l,), i64),
                        _sds((l,), i32), _sds((), i64))
    prune = (_sds((), i64), _sds((), i64), _sds((), i32))
    return table, attr, aidx, _sds((B,), i64), prune


def _lower_dense(floors, elide):
    table, attr, aidx, rankb, prune = _program_args()
    qmat = _sds((B, 7 + 2 * M), jnp.int64)
    return dk.calculate_deps_flat_attr.lower(
        table, attr, aidx, qmat, rankb, *prune, m=M, s=S, k=K,
        wide=dk.wide_codes(N, M, M), floors=floors, elide=elide)


def _lower_bucketed(floors, elide):
    table, attr, aidx, rankb, prune = _program_args()
    g, k_b, w, span = 128, 128, 16, 4
    i64, i32 = jnp.int64, jnp.int32
    cols = (i64, i64, i32, i32, i64, i64, i32, i32)
    buckets = dk.BucketTable(*(_sds((g, k_b), dt) for dt in cols),
                             *(_sds((w,), dt) for dt in cols))
    qmat = _sds((B, 7 + 2 * M + M * span), jnp.int64)
    return dk.bucketed_attr_jit.lower(
        table, attr, aidx, buckets, qmat, rankb, M, span, S, K, prune,
        keff=k_b, wide=dk.wide_codes(N, M, M), floors=floors, elide=elide)


@pytest.mark.parametrize("lower", [_lower_dense, _lower_bucketed],
                         ids=["calculate_deps_flat_attr",
                              "bucketed_attr_jit"])
def test_attributed_programs_hold_no_loop_without_their_search_legs(lower):
    """With the floor and elision legs off (their index lookups ARE binary
    searches) an attributed program is straight-line: sorts, gathers,
    scatters and prefix sums.  A ``while`` there is a search over the
    entry buffer come back."""
    text = lower(floors=False, elide=False).compile().as_text()
    assert not _WHILE.findall(text)


def test_a_search_compiles_to_the_loop_the_structural_test_looks_for():
    """The detector bites: ``jnp.searchsorted``'s default method is the
    ``while`` that ``_attr_post`` used to carry."""
    text = jax.jit(
        lambda re: jnp.searchsorted(re, jnp.arange(S, dtype=jnp.int64),
                                    side="right")
    ).lower(_sds((B,), jnp.int64)).compile().as_text()
    assert _WHILE.findall(text)
    # and the legs that keep their searches show as loops in the programs
    text = _lower_dense(floors=True, elide=True).compile().as_text()
    assert _WHILE.findall(text)
