"""SMS and SMS-DASH as registered `MemoryPolicy` objects.

The staged machinery lives in `repro.core.sms`; this module binds it to the
protocol. SMS-DASH is a *knob-point variant* — same stages, with the
deadline-aware stage-2 preemption pinned on via `configure_knobs` (the
`dash` value knob) — so it rides the registry instead of forking a second
config: `configure` stays the identity, and a knob grid can sweep `dash`
on plain "sms" without touching the registry at all.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import policy, sms as sms_lib


@policy.register
class SMS:
    name = "sms"
    variant_of = None
    # staged FIFO/DCS state shares nothing with the centralized CAM-buffer
    # schema — SMS-style protocols run the per-policy path
    stackable = False

    def configure(self, cfg):
        return cfg

    def init_state(self, cfg):
        return sms_lib.sms_state(cfg)

    # each stage under its own scope (`policy.STEP_SCOPES`)
    def tick(self, cfg, pool, st, sched, t):
        with jax.named_scope("sms.stage1"):
            st, sched = sms_lib.stage1_admit(cfg, st, sched, t)
        with jax.named_scope("sms.stage2"):
            st, sched = sms_lib.stage2_drain(cfg, pool, st, sched, t)
        return st, sched

    def select(self, cfg, pool, st, sched, dram, t):
        with jax.named_scope("sms.stage3"):
            return sms_lib.stage3_issue(cfg, st, sched, dram, t)

    # -- variable-step driver witness (see `policy.make_skip_step`) ---------
    def next_event(self, cfg, pool, st, sched, dram, t):
        return sms_lib.next_stage_event(cfg, st, sched, dram, t)

    def on_skip(self, cfg, sched, k):
        return sms_lib.skip_cycles(sched, k)

    # -- invariant-sanitizer hooks (repro.core.validate) --------------------
    def queued_requests(self, cfg, sched):
        return jnp.sum(sched["f_len"]) + jnp.sum(sched["d_len"])

    def check_invariants(self, cfg, pool, st, sched, t):
        return sms_lib.check_invariants(cfg, sched, t)

    def audit_skip(self, cfg, pool, st, sched, dram, t, t_new):
        return sms_lib.audit_skip(cfg, st, sched, dram, t, t_new)


@policy.register
class SMSDash(SMS):
    name = "sms_dash"
    variant_of = "sms"

    def configure_knobs(self, knobs):
        # SMS + deadline-aware stage 2 (paper §7 extension): dash is a
        # value knob, pinned True for this registry entry
        return knobs.replace(dash=True)
