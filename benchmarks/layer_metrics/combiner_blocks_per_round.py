"""Blocks one fused round of the read combiner carries: blocks served over
``ReadCombiner.rounds``, delta over the window."""


def read(win):
    blocks = win.delta("combiner.blocks")
    rounds = win.delta("combiner.rounds")
    if not blocks or not rounds:
        return None
    return blocks / rounds
