"""Class-distribution objectives from the paper (Sec. 5.1).

The global-imbalance objective of P1 (eq. 19) is the sum over edge nodes of
the Kullback-Leibler divergence between each edge's *virtual dataset* class
distribution H_j and the uniform reference Q (eq. 18).  The paper shows
(eq. 25-29) that minimizing it is equivalent to maximizing per-edge entropy,
which is in turn bounded by the pairwise-L1 class-count balancing objective
(eq. 29) that is linear in the assignment variables lambda_ij.

Everything here is pure jnp and jit-compatible; class information enters as a
count matrix ``class_counts[i, k]`` = number of samples of class k held by
EU i (the paper's c_k^i).
"""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp

_EPS = 1e-12


def edge_class_counts(lam: jnp.ndarray, class_counts: jnp.ndarray) -> jnp.ndarray:
    """Per-edge class counts under (possibly fractional) assignment.

    lam: (M, N) assignment weights lambda_ij (rows sum to 1 for SCA; DCA rows
         may sum to 2 with duplicate multicast updates).
    class_counts: (M, K) per-EU class histogram c_k^i.
    returns: (N, K) matrix  sum_i lam_ij * c_k^i    (numerator of eq. 28).
    """
    # HIGHEST: at default precision a TPU rounds the counts to bf16 (8-bit
    # mantissa), so counts in the thousands come back off by ~10
    return jnp.einsum("ij,ik->jk", lam, class_counts, precision=jax.lax.Precision.HIGHEST)


def edge_distributions(lam: jnp.ndarray, class_counts: jnp.ndarray) -> jnp.ndarray:
    """H_j(c_k) of eq. 28: normalized per-edge class distribution, (N, K)."""
    counts = edge_class_counts(lam, class_counts)
    return counts / jnp.maximum(counts.sum(axis=1, keepdims=True), _EPS)


def kld(h: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """D_KL(h || q) of eq. 18 for one distribution pair (K,)."""
    h = jnp.maximum(h, _EPS)
    q = jnp.maximum(q, _EPS)
    return jnp.sum(h * (jnp.log(h) - jnp.log(q)))


def total_kld_uniform(lam: jnp.ndarray, class_counts: jnp.ndarray) -> jnp.ndarray:
    """P1 objective (eq. 19): sum_j D_KL(H_j || Uniform)."""
    h = edge_distributions(lam, class_counts)
    k = class_counts.shape[1]
    q = jnp.full((k,), 1.0 / k)
    return jnp.sum(jax.vmap(lambda row: kld(row, q))(h))


def total_entropy(lam: jnp.ndarray, class_counts: jnp.ndarray) -> jnp.ndarray:
    """Sum of per-edge Shannon entropies chi_j (eq. 27); max'ing this == P1."""
    h = jnp.maximum(edge_distributions(lam, class_counts), _EPS)
    return -jnp.sum(h * jnp.log(h))


def edge_pairs(n_edges: int):
    """The set S of unordered edge pairs used in eq. 29."""
    return list(itertools.combinations(range(n_edges), 2))


def pairwise_l1_objective(lam: jnp.ndarray, class_counts: jnp.ndarray) -> jnp.ndarray:
    """Linearizable surrogate objective of P2 (eq. 29-30).

    sum_k sum_{(j,j') in S} | sum_i lam_ij c_k^i  -  sum_i lam_ij' c_k^i |

    Zero iff every class is split equally across all edges.
    """
    counts = edge_class_counts(lam, class_counts)  # (N, K)
    n = counts.shape[0]
    idx = jnp.asarray(edge_pairs(n))  # (P, 2)
    diff = counts[idx[:, 0]] - counts[idx[:, 1]]  # (P, K)
    return jnp.sum(jnp.abs(diff))


def divergence_bound(lam: jnp.ndarray, class_counts: jnp.ndarray) -> jnp.ndarray:
    """Weight-divergence upper bound of eq. 17 (up to the proportionality
    constant):  sum_j sigma_j * || H_j - p_global ||_1.

    sigma_j is the fraction of global data held at edge j; the L1 distance is
    between the edge class distribution and the *global* class distribution
    (the paper's ||D^{(j)}||_1).
    """
    counts = edge_class_counts(lam, class_counts)  # (N, K)
    totals = counts.sum(axis=1)  # (N,)
    sigma = totals / jnp.maximum(totals.sum(), _EPS)
    h = counts / jnp.maximum(totals[:, None], _EPS)
    global_counts = class_counts.sum(axis=0)
    p_global = global_counts / jnp.maximum(global_counts.sum(), _EPS)
    l1 = jnp.sum(jnp.abs(h - p_global[None, :]), axis=1)
    return jnp.sum(sigma * l1)
