"""stacked_us_per_cycle: device time of the stacked family program
(`_sim_batch_stacked`) per loop iteration, one simulated cycle of the whole
batch, in us, from the window's trace bursts (`stage_trace`); None when no
run of it iterated in a burst."""
import stage_trace as stt


def read(ctx):
    s = stt.from_ctx(ctx)
    return None if s is None else s.us_per_cycle(stt.STACKED)
