"""Blocks one collective round carries: ``IciWriteGroup.stats`` blocks over
rounds, delta over the window."""


def read(win):
    blocks, rounds = win.delta("ici.blocks"), win.delta("ici.rounds")
    if not blocks or not rounds:
        return None
    return blocks / rounds
