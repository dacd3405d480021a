"""The tests' oracle for StrictSerializabilityVerifier._check_cross_key: the
max-predecessor FIXPOINT the verifier ran until PR 30, kept as it was.  It
adds an edge for every pair of keys an op witnessed and propagates, per
node, the highest predecessor step of every key through a worklist, so an
op of n keys costs n*n edges and a scan expanded over a hundred keys is not
checkable with it; the verifier now sorts the same graph topologically with
one hub node an op (sim/verifier.py).  tests/test_verifier_differential.py
holds the two to the same verdict on seeded random histories."""

from collections import defaultdict, deque
from typing import Dict, Tuple

from accord_tpu.sim.verifier import (HistoryViolation,
                                     StrictSerializabilityVerifier)

_NEG = float("-inf")
_POS = float("inf")


class FixpointVerifier(StrictSerializabilityVerifier):

    def _check_cross_key(self) -> None:
        """Propagate max predecessors across keys and flag self-reachable
        steps (cycles) and real-time window inversions
        (ref StrictSerializabilityVerifier.java:58, Step.onChange)."""
        # -- build the happens-before edge set over (token, step) nodes
        edges = set()
        witnessed_until: Dict[Tuple[int, int], float] = {}
        written_before: Dict[Tuple[int, int], float] = {}
        written_after: Dict[Tuple[int, int], float] = {}

        for op_id, (start, end) in self.op_times.items():
            witness, read_step, appends = self._witnessed_steps(op_id)
            for token, s in witness.items():
                node = (token, s)
                if start > witnessed_until.get(node, _NEG):
                    witnessed_until[node] = start
                if end < written_before.get(node, _POS):
                    written_before[node] = end
                if appends.get(token) and start > written_after.get(node, _NEG):
                    written_after[node] = start
            # (a) anything witnessed coincident with step s_b of key b
            #     precedes step s_b+1 of b (ref Step.updatePeers +
            #     receiveKnowledgePhasedPredecessors via maxPeers)
            items = list(witness.items())
            for a, sa in items:
                for b, sb in items:
                    if a != b:
                        edges.add(((a, sa), (b, sb + 1)))
            # (b) keys only read precede the keys written by the same txn
            #     (ref Step.updatePredecessorsOfWrite)
            for b in appends:
                sb = witness.get(b)
                if sb is None or not appends[b]:
                    continue
                for a, ra in read_step.items():
                    if a != b:
                        edges.add(((a, ra), (b, sb)))

        # intra-key register order: (k, i) -> (k, i+1)
        max_step: Dict[int, int] = {}
        for (t, s) in (n for e in edges for n in e):
            if s > max_step.get(t, 0):
                max_step[t] = s
        for node in witnessed_until:
            t, s = node
            if s > max_step.get(t, 0):
                max_step[t] = s
        for t, final in self._effective_finals.items():
            if len(final) > max_step.get(t, 0):
                max_step[t] = len(final)
        for t, m in max_step.items():
            for i in range(m):
                edges.add(((t, i), (t, i + 1)))
                # a step is written after anything that witnessed its
                # direct predecessor state (ref propagateToDirectSuccessor)
                wu = witnessed_until.get((t, i))
                if wu is not None and wu > written_after.get((t, i + 1), _NEG):
                    written_after[(t, i + 1)] = wu

        # -- fixpoint: max predecessor per key + folded lower time bounds.
        # Monotone (steps and times only increase, both bounded), so a plain
        # worklist converges; this subsumes the ref's intrusive back-link
        # refresh queue.
        out_edges = defaultdict(list)
        for u, v in edges:
            out_edges[u].append(v)
        maxpred: Dict[Tuple[int, int], Dict[int, int]] = defaultdict(dict)
        lower = dict(written_after)   # serialization-point lower bounds
        work = deque(out_edges.keys())
        queued = set(work)
        while work:
            u = work.popleft()
            queued.discard(u)
            tu, su = u
            mu = maxpred.get(u)
            lu = lower.get(u, _NEG)
            for v in out_edges[u]:
                mv = maxpred[v]
                changed = False
                if mu:
                    for k, s in mu.items():
                        if mv.get(k, -1) < s:
                            mv[k] = s
                            changed = True
                if mv.get(tu, -1) < su:
                    mv[tu] = su
                    changed = True
                if lu > lower.get(v, _NEG):
                    lower[v] = lu
                    changed = True
                if changed and v not in queued and v in out_edges:
                    work.append(v)
                    queued.add(v)
            # nodes with no outgoing edges still get checked below

        for node, mp in maxpred.items():
            t, s = node
            if mp.get(t, -1) >= s:
                raise HistoryViolation(
                    f"cross-key cycle: key {t} step {s} reaches itself "
                    f"through happens-before relations (max predecessors "
                    f"{mp})")
        for node, lo in lower.items():
            hi = written_before.get(node, _POS)
            if lo > hi:
                t, s = node
                raise HistoryViolation(
                    f"real-time inversion on key {t} step {s}: must have "
                    f"been written after {lo} (a predecessor's bound) but "
                    f"was witnessed complete by {hi}")
