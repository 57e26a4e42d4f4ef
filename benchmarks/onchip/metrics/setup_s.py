"""Set-up: process start to the first timed request (JAX start-up,
weights, image pool, compile or cache load of every bucket, one served
step per bucket)."""


def read(run):
    return run.setup_s
