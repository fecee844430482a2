"""sparse_device_share.serve: percent of the device's busy time in the
serve step's ops under the ``dlrm.sparse`` named scope (the fused gather,
pool and interaction kernel, or the row-sharded gather and its exchange),
the mean over chips. Ops are mapped to scopes through the compiled step's
HLO metadata (`program_obs.step_scopes`)."""
import program_obs


def read(run):
    return program_obs.scope_share(run, ("dlrm.sparse",))
