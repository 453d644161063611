"""Share of the chip's HBM roofline that the scrypt step reaches, in %.

Each hash writes ROMix's state V (128 * r * N bytes) once and reads it
once: the least traffic the algorithm needs. Every full run of the step
hashes as many headers as its nonce argument holds, read from the shapes
the step was lowered with. Those bytes over the step's device time, over
the peak HBM bandwidth of ``peaks.json``. Bandwidth is the bound used:
ROMix's integer work has no sourced peak. Runs shorter than half the
longest are left out.
"""

PROGRAM = "jit__scrypt_step"
#: the step's name in the compile log, and the index of its nonces
LOWERED, NONCES_ARG = "_scrypt_step", 1


def read(run):
    if run.trace is None or LOWERED not in run.program_args:
        return None
    (batch,) = run.program_args[LOWERED][NONCES_ARG]
    cfg = run.config
    per_run = batch * 2 * 128 * cfg["scrypt_r"] * cfg["scrypt_N"]
    moved = seconds = 0.0
    for device in run.trace.devices:
        runs = [e - s for s, e, name in run.trace.programs(device) if name == PROGRAM]
        full = [d for d in runs if d >= 0.5 * max(runs, default=0)]
        moved += len(full) * per_run
        seconds += sum(full)
    if not seconds:
        return None
    return 100.0 * moved / seconds / run.peaks["hbm_bytes_per_s"]
