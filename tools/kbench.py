import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
from accord_tpu.ops.packing import startup
startup()
import jax, jax.numpy as jnp
from functools import partial


def launch_bench():
    """--launches: r08 launch-count microbench — device launches per 1k
    txns and wall clock for S small per-store deps scans dispatched solo
    vs ONE fused store-tagged launch (ops.deps_kernel.fused_flat_csr)."""
    from accord_tpu.ops import deps_kernel as dk
    S, N, Mi, B, QM, REPS = 16, 2048, 2, 4, 2, 32
    rng = np.random.default_rng(0)
    tables = []
    for _ in range(S):
        lo = rng.integers(0, 1 << 20, (N, Mi))
        tables.append(dk.DepsTable(
            jnp.asarray(rng.integers(1, 1 << 40, N)),
            jnp.asarray(rng.integers(0, 1 << 40, N)),
            jnp.asarray(rng.integers(1, 5, N).astype(np.int32)),
            jnp.asarray(rng.integers(0, 4, N).astype(np.int32)),
            jnp.asarray(np.full(N, 1, np.int32)),
            jnp.asarray(lo), jnp.asarray(lo + 64)))
    qm = np.zeros((S, B, 7 + 2 * QM), np.int64)
    qm[:, :, 0] = rng.integers(1 << 39, 1 << 41, (S, B))
    qm[:, :, 3] = 0b1111
    qm[:, :, 4:7] = qm[:, :, 0:3]
    qm[:, :, 7:7 + QM] = rng.integers(0, 1 << 20, (S, B, QM))
    qm[:, :, 7 + QM:] = qm[:, :, 7:7 + QM] + 64
    s_cap, k_cap = 16384, 64
    pz = (np.zeros(S, np.int64), np.zeros(S, np.int64),
          np.zeros(S, np.int32))

    def fetch(out):
        # the r10 two-stage shape: header join, then live entry prefix
        hdr = np.asarray(out[0])
        return hdr, np.asarray(out[1])

    # warm + compile both shapes
    fetch(dk.fused_flat_csr(tables, qm, pz, QM, s_cap, k_cap))
    for i in range(S):
        fetch(dk.calculate_deps_flat(tables[i], jnp.asarray(qm[i]),
                                     QM, s_cap, k_cap))
    t0 = time.perf_counter()
    for _ in range(REPS):
        for i in range(S):
            fetch(dk.calculate_deps_flat(
                tables[i], jnp.asarray(qm[i]), QM, s_cap, k_cap))
    solo = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(REPS):
        fetch(dk.fused_flat_csr(tables, qm, pz, QM, s_cap, k_cap))
    fused = time.perf_counter() - t0
    txns = REPS * S * B
    print(f"stores={S} flush={B}q reps={REPS} txns={txns}")
    print(f"solo : {REPS * S:5d} launches  "
          f"{1e3 * REPS * S / txns:7.1f}/1k txn  {solo * 1e3:8.1f} ms")
    print(f"fused: {REPS:5d} launches  "
          f"{1e3 * REPS / txns:7.1f}/1k txn  {fused * 1e3:8.1f} ms  "
          f"({solo / fused:.2f}x)")


if "--launches" in sys.argv:
    launch_bench()
    sys.exit(0)

B, P, K, G, N, M = 2048, 32, 128, 16384, 131072, 8
rng = np.random.default_rng(0)
blo = jnp.asarray(rng.integers(0, 1 << 40, (G, K)))
bhi = blo + 64
bslot = jnp.asarray(rng.integers(0, N, (G, K)).astype(np.int32))
qbuck = jnp.asarray(rng.integers(0, G, (B, P)).astype(np.int32))
qlo = jnp.asarray(rng.integers(0, 1 << 40, (B, M)))
qhi = qlo + 64
msb = jnp.asarray(rng.integers(0, 1 << 40, N))
status = jnp.asarray(rng.integers(0, 5, N).astype(np.int32))

def t(label, fn, *a):
    f = jax.jit(fn)
    f(*a).block_until_ready()
    ts = []
    for _ in range(3):
        t0 = time.perf_counter(); f(*a).block_until_ready(); ts.append(time.perf_counter()-t0)
    print(f"{label:30s} {min(ts)*1e3:8.1f} ms")

t("gather blo[g] [B,P,K] i64", lambda g: blo[jnp.clip(g,0)].sum(), qbuck)
t("gather bslot [B,P,K] i32", lambda g: bslot[jnp.clip(g,0)].sum(), qbuck)
def ovl(g):
    elo = blo[g]; ehi = bhi[g]
    ql = jnp.repeat(qlo, 4, axis=1)[:, :, None]
    qh = jnp.repeat(qhi, 4, axis=1)[:, :, None]
    return ((elo <= qh) & (ql <= ehi)).sum()
t("overlap [B,P,K]", ovl, qbuck)
cand = jnp.asarray(rng.integers(-1, N, (B, P*K)).astype(np.int32))
t("gather msb[cand] [B,C]", lambda c: msb[jnp.clip(c,0)].sum(), cand)
t("gather status[cand]+5col", lambda c: (msb[jnp.clip(c,0)] + status[jnp.clip(c,0)]).sum(), cand)
t("sort [B,C] i32", lambda c: jnp.sort(c, axis=1).sum(), cand)
t("topk k=64 [B,C]", lambda c: jax.lax.top_k(c, 64)[0].sum(), cand)
t("topk k=256 [B,C]", lambda c: jax.lax.top_k(c, 256)[0].sum(), cand)
scat_vals = jnp.asarray(rng.integers(0, N, (B, 64)).astype(np.int32))
pos = jnp.asarray(rng.integers(0, 180224, (B, 64)))
t("scatter B*64 -> s", lambda v, p: jnp.full(180225, -1, jnp.int32).at[p.reshape(-1)].set(v.reshape(-1), mode="drop").sum(), scat_vals, pos)
cum = jnp.asarray(rng.integers(0, 2, (B, P*K)).astype(np.int32))
t("cumsum axis1 [B,C]", lambda c: jnp.cumsum(c, axis=1).sum(), cum)
