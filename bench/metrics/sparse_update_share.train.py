"""sparse_update_share.train: percent of the device's busy time in the
train step's ops under ``dlrm.sparse_update`` alone (routing the pooled
gradients to rows and the scatter-add into the tables), mean over chips
(`program_obs.step_scopes`)."""
import program_obs


def read(run):
    return program_obs.scope_share(run, ("dlrm.sparse_update",))
