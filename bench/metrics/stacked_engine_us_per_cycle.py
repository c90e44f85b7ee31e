"""stacked_engine_us_per_cycle: device time of the ops under the cycle step's
`step.engine` scope in the stacked family program, per loop iteration, in us
(`stage_trace`); None where its ops carry no step scope."""
import stage_trace as stt


def read(ctx):
    s = stt.from_ctx(ctx)
    return None if s is None else \
        s.scope_us_per_cycle(stt.STACKED, "step.engine")
