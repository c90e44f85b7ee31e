"""`MemoryPolicy` protocol + registry: one scheduler API for the whole repo.

The paper's thesis is that a memory controller is three decoupled tasks
behind a common interface. This module is that interface. A policy is an
object with

    name         registry key ("frfcfs", "sms", "bliss", ...)
    variant_of   None, or the name of the policy this one is a configured
                 variant of (variants are excluded from the baseline sweep)
    configure(cfg)                    -> cfg     (static/shape adjustments
                                         only; value knobs go through
                                         configure_knobs — see below)
    configure_knobs(knobs)            -> knobs   (optional: pin value-like
                                         knobs, e.g. sms_dash sets dash=True;
                                         the default is identity)
    init_state(cfg)                   -> sched   (pytree of jax arrays)
    tick(cfg, pool, st, sched, t)     -> (st, sched)        admission +
                                         periodic policy maintenance
    select(cfg, pool, st, sched, dram, t) -> (st, sched, dram)  pick + issue

and the simulator is one generic `lax.scan` body (`make_step`) over whatever
policy object the registry hands back — no string dispatch anywhere.

Registering a policy:

    from repro.core import policy
    from repro.core.schedulers import CentralizedPolicy

    @policy.register
    class Oldest(CentralizedPolicy):
        name = "oldest"
        def score(self, cfg, pool, buf, is_hit, t):
            ...

`Registry` itself is domain-agnostic; `repro.serving.scheduler` uses a
second instance so the serving engine and the cycle sim enumerate policies
the same way.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Iterator, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import energy, engine, params, telemetry, validate
from repro.core.params import Knobs, SimConfig


class MemoryPolicy(Protocol):
    """Structural type for cycle-sim scheduling policies."""

    name: str
    variant_of: Optional[str]

    def configure(self, cfg: SimConfig) -> SimConfig: ...

    def init_state(self, cfg: SimConfig) -> Dict[str, Any]: ...

    def tick(self, cfg: SimConfig, pool, st, sched, t): ...

    def select(self, cfg: SimConfig, pool, st, sched, dram, t): ...


class Registry:
    """Ordered name -> object registry with a decorator interface.

    Mapping-style access (`reg["sms"]`, `reg["sms"] = obj`, `"sms" in reg`,
    `reg.keys()`) is supported so call sites and tests can treat a registry
    like the plain dicts it replaces.
    """

    def __init__(self, kind: str):
        self.kind = kind
        self._entries: Dict[str, Any] = {}

    def register(self, name: Optional[str] = None) -> Callable:
        """Use as ``@reg.register("name")`` or ``@reg.register`` (reads
        the object's ``name`` attribute)."""
        def deco(obj, _name=name if isinstance(name, str) else None):
            key = _name or getattr(obj, "name", None)
            if not key:
                raise ValueError(f"{self.kind} needs a `name` to register")
            if key in self._entries:
                raise ValueError(f"duplicate {self.kind} {key!r}")
            self._entries[key] = obj
            return obj

        if name is None or isinstance(name, str):
            return deco
        return deco(name)                       # bare @reg.register on a class

    def get(self, name: str) -> Any:
        try:
            return self._entries[name]
        except KeyError:
            raise KeyError(f"unknown {self.kind} {name!r}; "
                           f"registered: {', '.join(self._entries)}") from None

    def names(self) -> Tuple[str, ...]:
        return tuple(self._entries)

    def keys(self):
        return self._entries.keys()

    def items(self):
        return self._entries.items()

    def __contains__(self, name: str) -> bool:
        return name in self._entries

    def __iter__(self) -> Iterator[str]:
        return iter(self._entries)

    def __getitem__(self, name: str) -> Any:
        return self.get(name)

    def __setitem__(self, name: str, obj: Any) -> None:
        self._entries[name] = obj               # tests swap entries in-place


POLICY_REGISTRY = Registry("memory policy")


def register(cls):
    """Class decorator: instantiate and register a `MemoryPolicy`."""
    POLICY_REGISTRY.register(cls.name)(cls())
    return cls


def _ensure_builtin() -> None:
    # Lazy so `policy` stays import-cycle-free (policies import schedulers,
    # which imports engine); the built-ins self-register on first lookup.
    from repro.core import policies  # noqa: F401


def get(name: str) -> MemoryPolicy:
    _ensure_builtin()
    return POLICY_REGISTRY.get(name)


def names() -> Tuple[str, ...]:
    """All registered policies, in registration order."""
    _ensure_builtin()
    return POLICY_REGISTRY.names()


def baseline_names() -> Tuple[str, ...]:
    """Policies that are not configured variants of another policy."""
    _ensure_builtin()
    return tuple(n for n, p in POLICY_REGISTRY.items()
                 if getattr(p, "variant_of", None) is None)


def resolve_knobs(cfg: SimConfig, pol, knobs: Optional[Knobs] = None
                  ) -> Knobs:
    """The knob point a policy actually runs at: caller-supplied (or cfg
    defaults) filtered through the policy's optional `configure_knobs`."""
    kn = Knobs.from_cfg(cfg) if knobs is None else knobs
    ck = getattr(pol, "configure_knobs", None)
    return ck(kn) if ck is not None else kn


def is_stackable(name: str, cfg: SimConfig) -> bool:
    """True if `name` opts into the stacked cross-policy execution path.

    Stackability is declared by the policy (`stackable = True`, see
    `CentralizedPolicy`) AND requires `configure` to leave cfg untouched
    AND `configure_knobs` to be the identity at this config — stacked
    slices share one static config and, by default, cfg's knob point, so a
    policy that pins either (e.g. sms_dash's dash=True) must run the
    per-policy path.
    """
    pol = get(name)
    if not getattr(pol, "stackable", False) or pol.configure(cfg) != cfg:
        return False
    ck = getattr(pol, "configure_knobs", None)
    if ck is None:
        return True
    base = Knobs.from_cfg(cfg)
    resolved = ck(base)
    return all(np.asarray(getattr(resolved, f)) == np.asarray(getattr(base, f))
               for f in params.KNOB_FIELDS)


# The stages of the cycle step, as `jax.named_scope`s. A scope is trace-time
# metadata: it adds no primitive, and reaches every compiled op's `op_name`
# (`.../while/body/step.select/pol.select/sms.stage3/...`), so a device
# trace can be read per stage. The `step.*` scopes are disjoint at the top of
# a cycle; the others nest inside them. `step.telemetry`, `step.validate`
# and `step.skip` are traced only where telemetry, the sanitizer or the
# skipping driver is on. `make_stacked_step` uses `select.*` where the
# per-policy step has `pol.*`; the SMS stages add `sms.*`.
STEP_SCOPES = (
    "step.engine", "step.admit", "step.select", "step.telemetry",
    "step.validate", "step.skip",
    "pol.tick", "pol.select",
    "select.eligibility", "select.score", "select.issue", "select.clear",
    "sms.stage1", "sms.stage2", "sms.stage3",
)


def make_step(cfg: SimConfig, pol: MemoryPolicy, pool, active):
    """One simulator cycle, generic over the policy object.

    `pool`/`active` are read-only per-workload parameters: they are closed
    over here (broadcast into the trace) rather than threaded through the
    scan carry, which keeps the carry pytree to genuinely cycle-varying
    state only.
    """

    def step(carry, t):
        st, sched, dram = carry
        if cfg.telemetry_enabled:
            with jax.named_scope("step.telemetry"):
                snap = telemetry.snapshot(st, sched, dram)
        with jax.named_scope("step.engine"):
            st, dram = engine.completions_tick(st, dram, t)
            dram = energy.background_tick(cfg, dram, t)
            st = engine.deadline_tick(cfg, pool, st, t)
            st = engine.source_tick(cfg, pool, st, active, t)
        with jax.named_scope("step.admit"), jax.named_scope("pol.tick"):
            st, sched = pol.tick(cfg, pool, st, sched, t)
        with jax.named_scope("step.select"), jax.named_scope("pol.select"):
            st, sched, dram = pol.select(cfg, pool, st, sched, dram, t)
        if cfg.telemetry_enabled:
            with jax.named_scope("step.telemetry"):
                dram = telemetry.tick_accrue(cfg, pool, snap, st, sched,
                                             dram, t)
        if cfg.validate_enabled:
            # conservation laws hold as end-of-cycle identities
            with jax.named_scope("step.validate"):
                dram = dict(dram)
                dram["viol"] = dram["viol"] + validate.tick_counts(
                    cfg, pool, pol, st, sched, dram, t)
        return (st, sched, dram), None

    return step


def make_skip_step(cfg: SimConfig, pol: MemoryPolicy, pool, active):
    """Variable-step body: process cycle t fully, then jump to the next
    event (ROADMAP "Variable-step driver contract").

    Returns None when `pol` exposes no `next_event` witness — the driver
    then falls back to the ticked scan. The body runs the ordinary ticked
    `make_step` for cycle t, asks the engine + policy witnesses for the
    earliest cycle > t at which anything could happen, and replays the
    skipped span's closed-form accruals (source rng/instruction progress,
    background energy) in O(1). Hooks never observe the step size: they
    still see every processed cycle exactly as the ticked driver would.
    """
    if not hasattr(pol, "next_event"):
        return None
    step = make_step(cfg, pol, pool, active)
    on_skip = getattr(pol, "on_skip", None)

    def skip_body(carry, t, t_end):
        carry, _ = step(carry, t)
        st, sched, dram = carry
        with jax.named_scope("step.skip"):
            te = engine.next_source_event(cfg, pool, st, active, t)
            te = jnp.minimum(te, engine.next_completion(dram, t))
            te = jnp.minimum(te, pol.next_event(cfg, pool, st, sched, dram,
                                                t))
            t_new = jnp.minimum(te, t_end)
            k = t_new - t - 1                   # skipped cycles, >= 0
            st = engine.skip_sources(cfg, pool, st, active, k)
            if cfg.telemetry_enabled:
                # before energy.skip_accrue: reads the pre-span pd_down
                with jax.named_scope("step.telemetry"):
                    dram = telemetry.skip_accrue(cfg, pool, st, dram, t,
                                                 t_new)
            dram = energy.skip_accrue(cfg, dram, t, t_new)
            if on_skip is not None:
                sched = on_skip(cfg, sched, k)
        if cfg.validate_enabled:
            # lateness audit of the jumped span, on post-accrual state
            with jax.named_scope("step.validate"):
                dram = dict(dram)
                dram["viol"] = dram["viol"] + validate.span_counts(
                    cfg, pool, pol, st, sched, dram, active, t, t_new)
        return (st, sched, dram), t_new

    return skip_body
