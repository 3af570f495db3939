"""Recovery before the first resumed step: from constructing the trainer
on the crashed workdir to its request for the first resumed batch
(ledger validation, slot scan and read, checksum verification, device
put), mean over the window's cycles."""

import statistics


def read(obs):
    cycles = obs.get("restore_s")
    return statistics.fmean(cycles) if cycles else None
