"""Slots decoded per round over the engine's slots, mean over the
window's decode rounds."""


def read(run):
    occ = [r.slots for r in run.rounds if r.slots]
    if not occ:
        return None
    return sum(occ) / len(occ) / run.conf["engine"]["max_slots"]
