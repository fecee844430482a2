"""flush_host_ms: per flush, the host's own time in the serve path: the
mean of ``serve.assemble`` (concat and pad, the copy to the device) plus
``serve.dispatch`` (the step call) plus ``serve.copy_out`` (the copy back)
(program counters ``serve_<phase>_ms``), in ms. In a closed loop the
device idles for about this long per flush.

Every flush the session ran counts, set-up's warm-up flush among them: it
runs the compiled shape, compiled beforehand under ``serve.compile``. The
longest of each phase is logged, so a stalled flush names its phase."""
import program_obs

PHASES = ("assemble", "dispatch", "device_wait", "copy_out")
HOST = ("assemble", "dispatch", "copy_out")


def read(run):
    hs = {p: program_obs.histogram(f"serve_{p}_ms") for p in PHASES}
    if any(hs[p] is None for p in HOST):
        return None
    program_obs.log("longest flush phase: " + ", ".join(
        f"serve.{p} {hs[p]['max']:.1f} ms" for p in PHASES if hs[p]))
    return sum(hs[p]["sum"] for p in HOST) / hs["dispatch"]["count"]
