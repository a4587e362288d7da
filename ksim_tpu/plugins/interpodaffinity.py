"""InterPodAffinity filter + score kernels.

Upstream kube-scheduler v1.30 ``plugins/interpodaffinity/{filtering,
scoring}.go`` (the reference records this plugin's per-node outcomes via its
wrapped-plugin layer, reference simulator/scheduler/plugin/
wrappedplugin.go:420-548):

- Filter: (1) every required affinity term must have a matching existing
  pod in the candidate node's topology domain — unless NO pod in the
  cluster matches any term and the pod matches its own terms (the
  first-pod-of-a-series escape); a node missing any term's topology key
  fails.  (2) No required anti-affinity term may have a matching pod in
  the domain.  (3) No existing pod's required anti-affinity term that
  matches the incoming pod may have presence in the domain.  First failing
  check wins (upstream Filter order).
- Score: topology-pair weights accumulated from (a) the incoming pod's
  preferred (anti-)affinity terms over matching existing pods (+w / -w)
  and (b) existing pods' terms matched against the incoming pod —
  required-affinity terms at HardPodAffinityWeight, preferred at +-w
  (scoring.go processExistingPod).  NormalizeScore is
  ``int(100 * (s - min) / (max - min))`` over feasible nodes, all zeros
  when max == min.

Tensorization: the scan carry IS the per-node domain-count view
(state/interpod.py ``cnt_node``/``ecnt_node``/``ew_node`` [N,T] plus the
cluster-wide ``total`` [T]), so filter and score read it directly and
every per-pod check is a ``[N,T] x [T]`` matvec — vmapped over pods these
become ``[P,T] x [T,N]`` MXU matmuls.  Committing a pod is an elementwise
same-domain-mask outer-product add: the entire scan step contains no
gather, scatter, or segment reduction (each of those costs ~50us inside a
compiled TPU loop; elementwise [N,T] ops are effectively free).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ksim_tpu.plugins.base import MAX_NODE_SCORE, FilterOutput, NodeStateView, PodView
from ksim_tpu.state.interpod import InterPodTensors

NAME = "InterPodAffinity"

ERR_REASON_AFFINITY_RULES_NOT_MATCH = "node(s) didn't match pod affinity rules"
ERR_REASON_ANTI_AFFINITY_RULES_NOT_MATCH = "node(s) didn't match pod anti-affinity rules"
ERR_REASON_EXISTING_ANTI_AFFINITY_RULES_NOT_MATCH = (
    "node(s) didn't satisfy existing pods' anti-affinity rules"
)

AFFINITY_BIT = 1
ANTI_BIT = 2
EXISTING_ANTI_BIT = 4


class InterPodAffinity:
    # Static reason-bit width: result tensors downcast when every
    # filter plugin's bits fit a narrower dtype (engine/core.py).
    reason_bit_width = 3
    final_score_bound = 100  # post-normalize max (MaxNodeScore)
    name = NAME

    def __init__(self, ipa: InterPodTensors) -> None:
        del ipa  # all state flows through aux/carry

    def static_sig(self) -> tuple:
        return (NAME,)

    def failure_unresolvable(self, bits: int) -> bool:
        # Upstream: unmatched required affinity is UnschedulableAndUnresolvable
        # (removing pods can't create matches); anti-affinity violations are
        # Unschedulable (victims can clear them).
        return bool(bits & AFFINITY_BIT)

    # -- carried state ------------------------------------------------------

    def carry_init(self, aux) -> dict:
        a = aux["interpod"]
        return {
            "cnt": a["cnt_node"],
            "ecnt": a["ecnt_node"],
            "ew": a["ew_node"],
            "total": a["total"],
        }

    def carry_commit(self, carry, aux, pod: PodView, best) -> dict:
        a = aux["interpod"]
        j = pod.index
        placed = best >= 0
        b = jnp.maximum(best, 0)
        # Per-term same-domain mask [N, T]: node n is in the placed node's
        # domain for term t's topology key — one elementwise compare
        # against the placed node's row of the precomputed per-term domain
        # view (no gather/scatter in the scan step).
        doms_t = a["dom_t"][b]  # [T] the placed node's domain per term
        key_present = (doms_t >= 0) & placed  # [T]
        mask_t = (
            (a["dom_t"] == doms_t[None, :]) & key_present[None, :]
        ).astype(jnp.int32)  # [N, T] 0/1
        qm_t = a["pod_term_match"][j].astype(jnp.int32)  # [T]
        return {
            "cnt": carry["cnt"] + mask_t * qm_t[None, :],
            "ecnt": carry["ecnt"] + mask_t * a["pod_eat"][j][None, :],
            "ew": carry["ew"] + mask_t * a["pod_vw"][j][None, :],
            "total": carry["total"] + jnp.where(key_present, qm_t, 0),
        }

    # -- filter -------------------------------------------------------------

    def filter(self, state: NodeStateView, pod: PodView, aux, carry) -> FilterOutput:
        a = aux["interpod"]
        j = pod.index
        i32 = jnp.int32
        raff = a["req_aff"][j].astype(i32)  # [T]
        ranti = a["req_anti"][j].astype(i32)
        qm_t = a["pod_term_match"][j].astype(i32)  # [T]
        n = a["dom_t"].shape[0]

        def heavy(_):
            return self._filter_code(a, carry, raff, ranti, qm_t, j)

        # Upstream's PreFilter Skip (filtering.go): a pod with no required
        # (anti-)affinity terms of its own that also matches no existing
        # pod's term selectors cannot fail any of the three checks — the
        # heavy branch provably yields code 0 for it (every check dots
        # against raff/ranti/qm_t).  lax.cond skips the matvec work in the
        # sequential scan; under vmap it lowers to select (both branches,
        # as before).
        pred = (jnp.sum(raff) + jnp.sum(ranti) + jnp.sum(qm_t)) > 0
        code = jax.lax.cond(
            pred, heavy, lambda _: jnp.zeros(n, jnp.int32), None
        )
        return FilterOutput(ok=code == 0, reason_bits=code)

    def _filter_code(self, a, carry, raff, ranti, qm_t, j):
        i32 = jnp.int32
        dom_t = a["dom_t"]  # [N, T] constant
        cnt = carry["cnt"]  # [N, T]
        # (1) required affinity: all topology keys present AND every term's
        # domain count > 0 — or the global-empty + self-match escape.
        # Upstream keys affinityCounts by topologyPair (key, value) SHARED
        # across all of the pod's required terms (filtering.go
        # topologyToMatchedTermCount.update): two required terms with the
        # same topologyKey read one combined count, so a domain satisfying
        # either term satisfies both.  Aggregate this pod's per-term counts
        # over terms sharing a topology key before the <=0 check.
        missing_any = jnp.dot((dom_t < 0).astype(i32), raff) > 0  # [N]
        n_tk = a["node_dom"].shape[1]
        tk_onehot = (
            a["term_tk"][:, None] == jnp.arange(n_tk, dtype=a["term_tk"].dtype)[None, :]
        ).astype(i32)  # [T, TK]
        cnt_req = cnt * raff[None, :]  # this pod's required terms only
        key_cnt = cnt_req @ tk_onehot  # [N, TK] per-key totals
        need_key = (raff @ tk_onehot) > 0  # [TK] keys with required terms
        no_pods_any = jnp.any((key_cnt <= 0) & need_key[None, :], axis=1)
        escape = (jnp.dot(carry["total"], raff) == 0) & a["self_aff"][j]
        pass_aff = ~missing_any & (~no_pods_any | escape)
        # (2) incoming required anti-affinity (missing key = satisfied).
        viol_anti = jnp.dot((cnt > 0).astype(i32), ranti) > 0
        # (3) existing pods' required anti-affinity vs this pod.
        viol_existing = jnp.dot((carry["ecnt"] > 0).astype(i32), qm_t) > 0

        return jnp.where(
            ~pass_aff,
            AFFINITY_BIT,
            jnp.where(viol_anti, ANTI_BIT, jnp.where(viol_existing, EXISTING_ANTI_BIT, 0)),
        ).astype(i32)

    def decode_reasons(self, bits: int) -> list[str]:
        if bits & AFFINITY_BIT:
            return [ERR_REASON_AFFINITY_RULES_NOT_MATCH]
        if bits & ANTI_BIT:
            return [ERR_REASON_ANTI_AFFINITY_RULES_NOT_MATCH]
        if bits & EXISTING_ANTI_BIT:
            return [ERR_REASON_EXISTING_ANTI_AFFINITY_RULES_NOT_MATCH]
        return []

    # -- score --------------------------------------------------------------

    def score(self, state: NodeStateView, pod: PodView, aux, ok=None, carry=None) -> jnp.ndarray:
        a = aux["interpod"]
        j = pod.index
        qm_t = a["pod_term_match"][j].astype(jnp.int32)
        n = a["dom_t"].shape[0]

        def heavy(_):
            return (
                jnp.dot(carry["cnt"], a["pref_w"][j]) + jnp.dot(carry["ew"], qm_t)
            ).astype(jnp.int32)

        # Scoring Skip: no preferred weights of its own and no term
        # selector matching this pod -> both dot products are provably 0.
        pred = jnp.any(a["pref_w"][j] != 0) | jnp.any(qm_t > 0)
        return jax.lax.cond(pred, heavy, lambda _: jnp.zeros(n, jnp.int32), None)

    def normalize(self, scores: jnp.ndarray, ok: jnp.ndarray) -> jnp.ndarray:
        def heavy(_):
            big = jnp.iinfo(jnp.int32).max
            any_ok = jnp.any(ok)
            mn = jnp.where(any_ok, jnp.min(jnp.where(ok, scores, big)), 0)
            mx = jnp.where(any_ok, jnp.max(jnp.where(ok, scores, -big - 1)), 0)
            diff = mx - mn
            # Go: fScore = float64(MaxNodeScore) * (float64(s-min) /
            # float64(diff)); int64(fScore) truncates (values >= 0 ->
            # floor).  The ratio is of int32s, so the floor is computed in
            # INTEGER space: (100*(s-mn)) // diff is bit-identical to the
            # float64 result whenever 100*(s-mn) fits int32 (a raw score
            # span > ~21M — far beyond real clusters) and, unlike a float
            # division, identical on every XLA backend.  TPU's approximate
            # float32 divide truncated exact integer ratios one ulp low
            # (100*3166/3166 -> 99), the root cause of a 199-pod f32
            # churn drift between the TPU and the CPU (round 4).  Out-of-range spans fall back to the
            # old float path (f64 under x64 — exact; f32 otherwise, with
            # the documented +-1 boundary tolerance).
            shifted = scores - mn  # >= 0 on ok nodes (mn is their min)
            in_range = shifted < big // MAX_NODE_SCORE
            val_int = (
                jnp.where(in_range, shifted, 0) * MAX_NODE_SCORE
            ) // jnp.maximum(diff, 1)
            ftype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
            ratio = shifted.astype(ftype) / jnp.maximum(diff, 1).astype(ftype)
            val_f = jnp.floor(ftype(MAX_NODE_SCORE) * ratio).astype(jnp.int32)
            val = jnp.where(in_range, val_int, val_f)
            out = jnp.where(diff > 0, val, 0)
            return jnp.where(ok, out, 0).astype(jnp.int32)

        # All-zero raw scores normalize to all zeros (diff == 0 branch);
        # skip the float work for the majority of pods the score cond
        # already zeroed.
        return jax.lax.cond(
            jnp.any(scores != 0),
            heavy,
            lambda _: jnp.zeros(scores.shape[0], jnp.int32),
            None,
        )
