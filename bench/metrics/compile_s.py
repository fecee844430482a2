"""compile_s: seconds the serve session spent making each micro-batch
shape ready in set-up (program counter ``serve_compile_ms``, the
``serve.compile`` span: build the step, compile it or load it from the
compile cache, and run it once on a zero batch). No shape compiles inside
the window: a run that did is not correct."""
import program_obs


def read(run):
    h = program_obs.histogram("serve_compile_ms")
    return None if h is None else h["sum"] / 1e3
