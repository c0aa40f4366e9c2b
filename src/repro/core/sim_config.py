"""Unified simulator configuration (`SimConfig`) for every `simulate*`
entry point.

PRs 2–6 grew the `simulate` family ten shared keyword arguments (`slots`,
`warmup`, `queue`, `seed`, `tables`, `impl`, `scenario`, `schedule`,
`hist_bins`, and now `vcs`/`credits`); each new axis had to be threaded
through five signatures and three internal planners.  `SimConfig` bundles
them into ONE frozen value object:

    cfg = SimConfig(slots=1024, impl="batched", vcs=2,
                    scenario=Scenario.random_link_faults(g, 4))
    simulate(g, "uniform", 0.6, config=cfg)
    simulate_sweep(g, "uniform", loads, config=cfg, seeds=4)

Every entry point still accepts the historical kwargs — they are a thin
shim over `SimConfig.from_kwargs`, which raises when a kwarg is passed
ALONGSIDE a config carrying the same field (an ambiguous call is a bug at
the call site, never a silent preference).  Validation that used to be
duplicated per entry point (`scenario`/`schedule` mutual exclusion, impl
and vcs/credits checks) lives once in `__post_init__`, so every path
raises the same error.

New in this PR, the virtual-channel axis:

  * ``vcs`` — virtual channels per (node, port); 1 (default) is the
    single-FIFO pre-VC router, bitwise-unchanged.  ``vcs > 1`` switches
    to the credit-flow VC router (VC0 = restricted-DOR escape lane,
    VCs 1.. = credit-aware adaptive lanes — see docs/simulator.md).
  * ``credits`` — per-(port, VC) credit window (advertised downstream
    buffer space); None means the full queue depth.  Must satisfy
    ``2 <= credits <= queue`` (a window of 1 cannot admit the 2-slot
    injection/turn bubble, so it would silence the escape lane).
"""
from __future__ import annotations

from dataclasses import dataclass, fields, replace

from .fault_schedule import FaultSchedule
from .lattice import InfeasibleNetwork
from .link_spec import LinkSpec
from .scenario import Scenario

SIM_IMPLS = ("batched", "reference", "fused")


def validate_feature_combo(*, impl: str | None = None, vcs: int = 1,
                           links_trivial: bool = True,
                           express: bool = False,
                           policy: str = "dor") -> None:
    """The single source of truth for unsupported feature combinations.

    `SimConfig.__post_init__` calls this with the user-facing fields;
    `simulation._make_ctx` / `_get_runner` call it again with the resolved
    context so direct internal callers hit the SAME actionable message.
    Passing `impl=None` skips the impl-specific cells (not yet known).

    The remaining exclusion cells of the feature-compatibility matrix
    (docs/simulator.md) are:

      * fused × vcs>1            — the Pallas kernel is V=1-only
      * fused × non-trivial links — the kernel is weight-1/no-overlay
      * express × vcs=1 × adaptive/escape policy — faulted express
        fabrics at V=1 route with greedy weighted DOR only; the V=1
        adaptive/escape heuristics score base-lattice ports
    """
    if impl == "fused":
        if vcs > 1:
            raise InfeasibleNetwork(
                "impl='fused' (the Pallas slot-step kernel) is V=1-only"
                "; run vcs>1 with impl='batched' or 'reference' (see "
                "docs/simulator.md, 'Virtual channels & credit flow')")
        if not links_trivial:
            raise InfeasibleNetwork(
                "impl='fused' (the Pallas slot-step kernel) is "
                "weight-1/no-overlay-only; run heterogeneous "
                "LinkSpecs with impl='batched' or 'reference' "
                "(see docs/simulator.md, 'Heterogeneous links')")
    if express and vcs == 1 and policy in ("adaptive", "escape"):
        raise InfeasibleNetwork(
            f"express-channel overlays at vcs=1 route with greedy "
            f"weighted DOR only (dead express hops fall back to base "
            f"ports); the V=1 {policy!r} policy scores base-lattice "
            f"ports — use policy='dor' or the VC router (vcs >= 2, "
            f"whose adaptive lanes and escape fallback understand the "
            f"extended port axis)")

# fields an entry point may also receive as a legacy kwarg; used by
# `from_kwargs` to build the config and to name conflicts precisely
_FIELD_NAMES: tuple[str, ...] = (
    "slots", "warmup", "queue", "seed", "tables", "impl", "scenario",
    "schedule", "hist_bins", "vcs", "credits", "links")


@dataclass(frozen=True)
class SimConfig:
    """Frozen bundle of every run-shaping `simulate*` parameter (the
    per-call inputs — graph, pattern, loads, seeds, fold — stay call
    arguments: they name *what* to run, the config names *how*)."""

    slots: int = 512
    warmup: int = 128
    queue: int = 4
    seed: int = 0
    tables: object | None = None        # SimTables; kept untyped to avoid
    impl: str = "batched"               # a circular simulation import
    scenario: Scenario | None = None
    schedule: FaultSchedule | None = None
    hist_bins: int = 0
    vcs: int = 1
    credits: int | None = None
    links: LinkSpec | None = None

    def __post_init__(self):
        if self.impl not in SIM_IMPLS:
            raise ValueError(
                f"unknown simulator impl {self.impl!r}; expected one of "
                f"{SIM_IMPLS}")
        if self.scenario is not None and self.schedule is not None:
            # the one shared home of the exclusivity check every entry
            # point used to duplicate — keep the historical message
            raise ValueError("pass either scenario= or schedule=, not both")
        if self.slots <= 0:
            raise ValueError(f"slots must be positive, got {self.slots}")
        if not 0 <= self.warmup <= self.slots:
            raise ValueError(
                f"need 0 <= warmup <= slots, got warmup={self.warmup} "
                f"slots={self.slots}")
        if self.queue < 2:
            raise ValueError(
                f"queue must be >= 2 (the bubble rule needs 2 free slots "
                f"to admit a packet), got {self.queue}")
        if self.hist_bins < 0:
            raise ValueError(
                f"hist_bins must be >= 0, got {self.hist_bins}")
        if self.vcs < 1:
            raise ValueError(f"vcs must be >= 1, got {self.vcs}")
        if self.credits is not None:
            if self.vcs == 1:
                raise ValueError(
                    "credits= is part of the VC credit-flow router; it "
                    "needs vcs >= 2 (the single-FIFO vcs=1 router has no "
                    "credit counters)")
            if not 2 <= self.credits <= self.queue:
                raise ValueError(
                    f"need 2 <= credits <= queue={self.queue} (a window "
                    f"below 2 starves the injection/turn bubble), got "
                    f"{self.credits}")
        if self.links is not None and not isinstance(self.links, LinkSpec):
            raise TypeError(
                f"links= expects a LinkSpec, got "
                f"{type(self.links).__name__}")
        if self.schedule is not None:
            policy = self.schedule.policy
        elif self.scenario is not None:
            policy = self.scenario.policy
        else:
            policy = "dor"
        validate_feature_combo(
            impl=self.impl, vcs=self.vcs,
            links_trivial=self.links is None or self.links.is_trivial,
            express=bool(self.links is not None and self.links.express),
            policy=policy)

    # -- the legacy-kwarg shim ---------------------------------------------
    @classmethod
    def from_kwargs(cls, config: "SimConfig | None" = None,
                    **kwargs) -> "SimConfig":
        """Resolve `config=` plus legacy per-call kwargs into one
        `SimConfig`.  kwargs valued None mean "not passed" (every legacy
        kwarg is declared with a None default); passing a real value for
        a field while also passing `config` raises — the call is
        ambiguous, and silently preferring either side would hide bugs.
        """
        unknown = set(kwargs) - set(_FIELD_NAMES)
        if unknown:
            raise TypeError(
                f"unknown simulate kwargs: {sorted(unknown)}; SimConfig "
                f"fields are {list(_FIELD_NAMES)}")
        given = {k: v for k, v in kwargs.items() if v is not None}
        if config is None:
            return cls(**given)
        if not isinstance(config, cls):
            raise TypeError(
                f"config= expects a SimConfig, got {type(config).__name__}")
        if given:
            raise ValueError(
                f"both config= and legacy kwarg(s) {sorted(given)} were "
                "passed; put every run parameter on the SimConfig (e.g. "
                "replace(config, ...)) or drop config= and use kwargs")
        return config

    def replace(self, **changes) -> "SimConfig":
        """`dataclasses.replace` convenience (re-validates)."""
        return replace(self, **changes)

    def run_kwargs(self) -> dict:
        """The config as the keyword dict internal planners consume."""
        return {f.name: getattr(self, f.name) for f in fields(self)}
