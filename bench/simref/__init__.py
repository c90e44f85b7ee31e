"""The benchmark's plain reference and its frozen traffic and metric code.

Nothing here imports the program, and no later change to the program moves
it:
  loop       the reference simulator: one workload row under one policy,
             cycle by cycle, in plain Python loops
  workloads  the archetype tables, the mix sampler and the pool builders
             every cell's traffic is drawn from (a frozen copy of the
             program's, checked against it in every run)
  metrics    the per-row metric arithmetic `run_sweep` reports (a frozen
             copy of the program's)
  params     requester classes and the configuration record
"""
