"""Engine step: device time of one call of a prefill chunk (the programs
``jit_chunk``, ``ServeEngine.prefill_chunk``, every chunk length), in ms,
from the trace."""


def read(run):
    if run.trace is None:
        return None
    s = run.trace.program_s("jit_chunk")
    return None if s is None else 1e3 * s
