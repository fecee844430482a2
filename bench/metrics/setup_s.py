"""setup_s: seconds from process start to the first timed request or step:
device set-up, weights made on the device, compiles or cache loads,
warm-up and traffic generation (host clock)."""


def read(run):
    return run.setup_s
