"""Lattice graphs G(M) (paper Definition 3) with exact construction and
vectorised distance analysis.

A lattice graph is the Cayley graph of Z^n/MZ^n with generator set {±e_i}.
Nodes are labelled by the Hermite box {x : 0 ≤ x_i < H_ii} (Definition 26),
indexed in mixed radix so that index 0 is the origin.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import cached_property

import numpy as np

from . import intmat


class InfeasibleNetwork(ValueError):
    """The network leaves nothing to measure or run: fewer than two live
    nodes, no reachable pair, or a feature combination the simulator does
    not support.  A `ValueError`, so existing callers that catch that
    still do; the topology explorer scores exactly these as the worst
    candidate and lets every other error propagate."""


@dataclass(frozen=True)
class LatticeGraph:
    """G(M): |det M| nodes, regular of degree 2n."""

    M: tuple[tuple[int, ...], ...]

    def __init__(self, M):
        A = intmat.as_np(M)
        object.__setattr__(self, "M", tuple(tuple(int(x) for x in row) for row in A))

    # -- basic invariants ---------------------------------------------------
    @cached_property
    def matrix(self) -> np.ndarray:
        return intmat.as_np(self.M)

    @cached_property
    def n(self) -> int:
        return self.matrix.shape[0]

    @cached_property
    def hermite(self) -> np.ndarray:
        return intmat.hermite_normal_form(self.matrix)

    @cached_property
    def order(self) -> int:
        return abs(intmat.det(self.matrix))

    @cached_property
    def degree(self) -> int:
        return 2 * self.n

    @cached_property
    def sides(self) -> np.ndarray:
        """Hermite diagonal: the mixed-radix sizes of the labelling box."""
        return np.diagonal(self.hermite).copy()

    # -- labelling ----------------------------------------------------------
    @cached_property
    def strides(self) -> np.ndarray:
        """Mixed-radix strides: index(v) = Σ v_i · stride_i."""
        s = np.ones(self.n, dtype=np.int64)
        sides = self.sides
        for i in range(self.n - 2, -1, -1):
            s[i] = s[i + 1] * sides[i + 1]
        return s

    @cached_property
    def labels(self) -> np.ndarray:
        """(N, n) array of all node labels in index order."""
        grids = np.meshgrid(*[np.arange(a) for a in self.sides], indexing="ij")
        return np.stack([g.ravel() for g in grids], axis=-1).astype(np.int64)

    def label_to_index(self, v) -> np.ndarray:
        """Map arbitrary integer vectors (..., n) to node indices."""
        lab = intmat.canonical_label(v, self.hermite)
        return (lab * self.strides).sum(axis=-1)

    # -- adjacency ----------------------------------------------------------
    @cached_property
    def neighbor_indices(self) -> np.ndarray:
        """(N, 2n) neighbour index table; column 2i is +e_{i+1}, 2i+1 is −e_{i+1}."""
        labs = self.labels
        cols = []
        eye = np.eye(self.n, dtype=np.int64)
        for i in range(self.n):
            cols.append(self.label_to_index(labs + eye[i]))
            cols.append(self.label_to_index(labs - eye[i]))
        return np.stack(cols, axis=-1)

    def edges(self) -> np.ndarray:
        """(E, 2) undirected edge list (u < v after dedup of parallel edges)."""
        N = self.order
        src = np.repeat(np.arange(N), 2 * self.n)
        dst = self.neighbor_indices.ravel()
        e = np.stack([np.minimum(src, dst), np.maximum(src, dst)], axis=-1)
        return np.unique(e, axis=0)

    # -- distances ----------------------------------------------------------
    @cached_property
    def distances_from_origin(self) -> np.ndarray:
        """Single-source BFS distances.  Because G(M) is vertex-transitive
        (Cayley), the distance profile from node 0 is the profile from any
        node; dist(u, v) = dist(0, v − u)."""
        N = self.order
        dist = np.full(N, -1, dtype=np.int64)
        dist[0] = 0
        frontier = np.array([0], dtype=np.int64)
        d = 0
        nbr = self.neighbor_indices
        while frontier.size:
            d += 1
            nxt = np.unique(nbr[frontier].ravel())
            nxt = nxt[dist[nxt] < 0]
            dist[nxt] = d
            frontier = nxt
        return dist

    @cached_property
    def diameter(self) -> int:
        return int(self.distances_from_origin.max())

    @cached_property
    def average_distance(self) -> float:
        """Mean distance over ordered pairs with distinct endpoints, i.e.
        Σ_v d(0,v) / (N−1) — the convention matching the paper's Table 1."""
        d = self.distances_from_origin
        return float(d.sum()) / (self.order - 1)

    def distance(self, u, v) -> int:
        """d(u, v) via translation invariance."""
        u = np.asarray(u, dtype=np.int64)
        v = np.asarray(v, dtype=np.int64)
        return int(self.distances_from_origin[self.label_to_index(v - u)])

    def distance_distribution(self) -> np.ndarray:
        """hist[k] = #nodes at distance k from any fixed node."""
        return np.bincount(self.distances_from_origin)

    # -- structure ----------------------------------------------------------
    @cached_property
    def side(self) -> int:
        """The side a of the graph (Definition 7): H[n-1, n-1]."""
        return int(self.hermite[self.n - 1, self.n - 1])

    def projection(self) -> "LatticeGraph":
        """Projection over e_n (Definition 7): G(B) for H = [[B, c], [0, a]]."""
        if self.n == 1:
            raise ValueError("cannot project a cycle")
        return LatticeGraph(self.hermite[: self.n - 1, : self.n - 1])

    def order_of(self, x) -> int:
        return intmat.element_order(x, self.matrix)

    def is_connected(self) -> bool:
        return bool((self.distances_from_origin >= 0).all())

    def smith_invariants(self) -> tuple[int, ...]:
        return intmat.smith_invariants(self.matrix)

    def __repr__(self) -> str:  # pragma: no cover
        return f"LatticeGraph(n={self.n}, N={self.order}, M={list(map(list, self.M))})"
