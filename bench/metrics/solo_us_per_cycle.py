"""solo_us_per_cycle: device time of the per-policy programs (`_sim_batch`:
`sms` and `sms_dash` in the cell) per loop iteration, one simulated cycle
of the whole batch, in us, from the window's trace bursts (`stage_trace`);
None when no run of them iterated in a burst."""
import stage_trace as stt


def read(ctx):
    s = stt.from_ctx(ctx)
    return None if s is None else s.us_per_cycle(stt.SOLO)
