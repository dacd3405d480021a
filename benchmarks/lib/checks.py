"""What decides ``correct`` (copied from chip_smoke.py: _deps_digest,
LADDER, _verifier, _missing_acks)."""

import hashlib

LADDER = ("n_device_faults", "n_quarantines", "n_fallback_queries",
          "n_shadow_mismatches")
DEVICE_ROUTES = ("n_bucketed_queries", "n_dense_queries", "n_fused_queries",
                 "n_mesh_queries")
ROUTE_COUNTERS = DEVICE_ROUTES + ("n_host_queries", "n_dispatches")


def deps_digest(built):
    """Canonical bytes of a batch of built Deps (CSR columns + packed ids),
    hashed: equal digests == byte-equal answers."""
    h = hashlib.sha256()
    for d in built:
        kd, rd = d.key_deps, d.range_deps
        doc = (kd.to_csr(), [(t.msb, t.lsb, t.node) for t in kd.txn_ids],
               rd.to_csr(), [(t.msb, t.lsb, t.node) for t in rd.txn_ids])
        h.update(repr(doc).encode())
    return h.hexdigest()


def device_counters(devs):
    """Route + ladder counters and kernel_times summed over DeviceStates,
    plus the download byte counts."""
    rep = {k: int(sum(getattr(d, k) for d in devs))
           for k in ROUTE_COUNTERS + LADDER
           + ("download_bytes", "attr_download_bytes", "n_ticks",
              "n_host_ticks")}
    rep["host_pinned"] = any(d.host_pinned for d in devs)
    kt = {}
    for d in devs:
        for kind, (calls, secs) in d.kernel_times.items():
            cell = kt.setdefault(kind, [0, 0.0])
            cell[0] += calls
            cell[1] += secs
    rep["kernel_times"] = kt
    return rep


def counters_delta(after, before):
    out = {}
    for k, v in after.items():
        if k == "kernel_times":
            out[k] = {kind: [c - before[k].get(kind, [0, 0.0])[0],
                             s - before[k].get(kind, [0, 0.0])[1]]
                      for kind, (c, s) in v.items()}
        elif isinstance(v, bool):
            out[k] = v
        else:
            out[k] = v - before[k]
    return out


def ladder_problems(rep):
    """The no-hidden-fallback gate: the ladder serves a broken device path
    from the host bit-identically, so its counters are the only place a
    fault shows."""
    problems = [f"{k}={rep[k]}" for k in LADDER if rep[k]]
    if rep["host_pinned"]:
        problems.append("host_pinned")
    return problems


def verifier():
    from accord_tpu.sim.elle import CompositeVerifier, ListAppendCycleChecker
    from accord_tpu.sim.verifier import StrictSerializabilityVerifier
    return CompositeVerifier(StrictSerializabilityVerifier(),
                             ListAppendCycleChecker())


def missing_acks(acked, finals):
    """Acknowledged appends that a final read of their key does not hold."""
    return [(k, v) for k, vals in sorted(acked.items()) for v in vals
            if v not in finals.get(k, ())]
