"""The first resumed step: from the request for its batch to the end of
``run`` (the trainer's step program found again in the compile cache,
the step, its ledger record), mean over the window's cycles."""

import statistics


def read(obs):
    cycles = obs.get("resume_step_s")
    return statistics.fmean(cycles) if cycles else None
