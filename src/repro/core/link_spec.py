"""Heterogeneous-link specification: weighted latencies, sparse Z-pillars,
express channels.

The slot simulator (`core/simulation.py`) historically assumed every hop
costs exactly one slot over a fixed 2n-port torus/lattice neighbourhood.
Real 3D fabrics are not uniform: TSV-style Z-links run slower than
in-plane links, vertical connectivity may exist only at sparse *pillar*
coordinates, and *express* channels spanning several hops of one
dimension are the standard latency fix (see ROADMAP "Heterogeneous
links" and the NoC-3D exemplars in SNIPPETS.md).  `LinkSpec` is the
declarative description of all three axes:

  * ``dim_weights`` — per-dimension integer slot cost ``w >= 1`` of one
    hop.  A packet crossing a weight-w channel holds it for w slots and
    only becomes eligible downstream after those w slots have elapsed.
  * ``pillar_dim``/``pillar_every`` — Z-connectivity restricted to
    pillar nodes: node u keeps its ``pillar_dim`` links iff every OTHER
    label coordinate is ``0 (mod pillar_every)``.  Compiles to a static
    (N, 2n) structural mask AND-ed into the scenario/schedule ``link_ok``
    masks (so the dead-channel audit covers missing pillars for free).
  * ``express`` — extra long links: each ``(dim, span, weight)`` entry
    appends a +/- port pair connecting u to u ± span·e_dim with its own
    slot cost.  Express ports extend the port axis to P = 2n + 2·X and
    participate in greedy weighted-DOR routing (largest usable span
    first), so the minimal-record invariant is preserved: a span-s hop
    is only taken when the remaining offset in that dimension is >= s.

A default-constructed spec (``LinkSpec()``) is *trivial* — every
consumer treats it exactly like ``None`` and compiles the identical
pre-heterogeneous program (the bitwise weight-1 contract pinned by
``tests/test_hetero_links.py``).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from .lattice import InfeasibleNetwork


@dataclass(frozen=True)
class LinkSpec:
    """Frozen, hashable description of a heterogeneous link overlay.

    All fields default to the trivial (uniform weight-1, full
    connectivity, no overlay) spec.  Dimension indices are validated
    lazily against the graph (``validate(n)``) because the spec is
    constructed before a lattice is bound.
    """

    dim_weights: tuple[int, ...] = ()
    pillar_dim: int | None = None
    pillar_every: int = 1
    express: tuple[tuple[int, int, int], ...] = field(default_factory=tuple)

    def __post_init__(self):
        object.__setattr__(self, "dim_weights",
                           tuple(int(w) for w in self.dim_weights))
        object.__setattr__(self, "express",
                           tuple((int(d), int(s), int(w))
                                 for d, s, w in self.express))
        if any(w < 1 for w in self.dim_weights):
            raise ValueError("dim_weights must all be >= 1, got "
                             f"{self.dim_weights}")
        if self.pillar_every < 1:
            raise ValueError("pillar_every must be >= 1")
        if self.pillar_dim is not None and self.pillar_dim < 0:
            raise ValueError("pillar_dim must be a dimension index >= 0")
        seen = set()
        for d, s, w in self.express:
            if s < 2:
                raise ValueError(
                    f"express span must be >= 2 (got {s}); a span-1 "
                    "express link duplicates the base channel — use "
                    "dim_weights instead")
            if w < 1:
                raise ValueError(f"express weight must be >= 1, got {w}")
            if d < 0:
                raise ValueError("express dim must be >= 0")
            if (d, s) in seen:
                raise ValueError(
                    f"duplicate express entry for (dim={d}, span={s})")
            seen.add((d, s))
        if self.express and self.has_pillar:
            raise ValueError(
                "express overlays and pillar masks cannot be combined "
                "in one LinkSpec (express channels require the full "
                "base connectivity to fall back on)")

    # -- classification -----------------------------------------------------

    @property
    def has_pillar(self) -> bool:
        """True when the spec removes any links (pillar_every >= 2)."""
        return self.pillar_dim is not None and self.pillar_every > 1

    @property
    def weighted(self) -> bool:
        """True when any channel costs more than one slot."""
        return any(w > 1 for w in self.dim_weights) or \
            any(w > 1 for _, _, w in self.express)

    @property
    def is_trivial(self) -> bool:
        """True when the spec changes nothing: every consumer must then
        compile the exact same program as ``links=None``."""
        return (not self.weighted and not self.has_pillar
                and not self.express)

    def validate(self, n: int) -> None:
        """Check dimension indices against an n-dimensional lattice."""
        if self.dim_weights and len(self.dim_weights) != n:
            raise ValueError(
                f"dim_weights has {len(self.dim_weights)} entries for an "
                f"n={n} lattice")
        if self.pillar_dim is not None and self.pillar_dim >= n:
            raise ValueError(f"pillar_dim {self.pillar_dim} out of range "
                             f"for n={n}")
        for d, s, w in self.express:
            if d >= n:
                raise ValueError(f"express dim {d} out of range for n={n}")

    def fingerprint(self):
        """Hashable identity for compile caches (None-like when trivial)."""
        if self.is_trivial:
            return None
        return (self.dim_weights, self.pillar_dim, self.pillar_every,
                self.express)

    # -- port geometry ------------------------------------------------------
    # Port layout: base ports 2d (+e_d) and 2d+1 (-e_d) for d < n, then
    # one +/- pair per express entry: port 2n+2j = +span_j·e_{dim_j},
    # port 2n+2j+1 its opposite.  This keeps both structural invariants
    # the whole simulator relies on: opp(p) == p ^ 1, and
    # nbr[nbr[u, p], p ^ 1] == u.

    def num_ports(self, n: int) -> int:
        return 2 * n + 2 * len(self.express)

    def port_dims(self, n: int) -> np.ndarray:
        """(P,) dimension index of each port."""
        base = np.repeat(np.arange(n), 2)
        ext = np.repeat([d for d, _, _ in self.express], 2).astype(np.int64)
        return np.concatenate([base, ext]).astype(np.int32)

    def port_signs(self, n: int) -> np.ndarray:
        """(P,) +1 for even (forward) ports, -1 for odd ones."""
        P = self.num_ports(n)
        return np.where(np.arange(P) % 2 == 0, 1, -1).astype(np.int32)

    def port_spans(self, n: int) -> np.ndarray:
        """(P,) hop span of each port (1 for base, span for express)."""
        base = np.ones(2 * n, dtype=np.int32)
        ext = np.repeat([s for _, s, _ in self.express], 2).astype(np.int32)
        return np.concatenate([base, ext]).astype(np.int32)

    def port_weights(self, n: int) -> np.ndarray:
        """(P,) slot cost of crossing each port's channel."""
        dw = self.dim_weights if self.dim_weights else (1,) * n
        base = np.repeat(np.asarray(dw, dtype=np.int32), 2)
        ext = np.repeat([w for _, _, w in self.express], 2).astype(np.int32)
        return np.concatenate([base, ext]).astype(np.int32)

    def hop_table(self, n: int) -> np.ndarray:
        """(P, n) signed label displacement of each port."""
        P = self.num_ports(n)
        hop = np.zeros((P, n), dtype=np.int32)
        hop[np.arange(P), self.port_dims(n)] = \
            self.port_signs(n) * self.port_spans(n)
        return hop

    # -- graph binding ------------------------------------------------------

    def extended_neighbors(self, g) -> np.ndarray:
        """(N, P) neighbour table: base columns are ``g.neighbor_indices``,
        express columns resolved through ``g.label_to_index`` so overlay
        links respect the lattice quotient exactly like base links."""
        self.validate(g.n)
        nbr = np.asarray(g.neighbor_indices, dtype=np.int32)
        if not self.express:
            return nbr
        labels = np.asarray(g.labels)
        cols = [nbr]
        for d, s, _ in self.express:
            step = np.zeros(g.n, dtype=labels.dtype)
            step[d] = s
            fwd = np.asarray(g.label_to_index(labels + step), dtype=np.int32)
            bwd = np.asarray(g.label_to_index(labels - step), dtype=np.int32)
            if (fwd == np.arange(g.order)).any():
                raise InfeasibleNetwork(
                    f"express (dim={d}, span={s}) folds onto a self-loop "
                    "on this lattice — span matches the cycle length")
            cols.append(np.stack([fwd, bwd], axis=1))
        return np.concatenate(cols, axis=1).astype(np.int32)

    def structural_mask(self, g) -> np.ndarray | None:
        """(N, 2n) bool pillar mask, or None when every link exists.

        Node u is a *pillar* iff all label coordinates OTHER than
        ``pillar_dim`` are 0 mod ``pillar_every``; only pillars keep
        their ``pillar_dim`` channels.  The mask is automatically
        symmetric: u and its dim-d neighbour share every non-d
        coordinate, so they are pillars together.
        """
        if not self.has_pillar:
            return None
        self.validate(g.n)
        labels = np.asarray(g.labels)
        other = np.arange(g.n) != self.pillar_dim
        is_pillar = (labels[:, other] % self.pillar_every == 0).all(axis=1)
        mask = np.ones((g.order, 2 * g.n), dtype=bool)
        mask[:, 2 * self.pillar_dim] = is_pillar
        mask[:, 2 * self.pillar_dim + 1] = is_pillar
        return mask
