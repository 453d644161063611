"""Seconds from the run's start to the window's: starting the
coordinator and the worker, the miner reaching the chip, and the
warm-up jobs with every trace, compile and cache load they cause."""


def read(run):
    return run.setup_s
