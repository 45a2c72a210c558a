"""Engine step: device time of one call of the slot decode step (the
program ``jit_step``, ``ServeEngine.step``), in ms, from the trace."""


def read(run):
    if run.trace is None:
        return None
    s = run.trace.program_s("jit_step")
    return None if s is None else 1e3 * s
