"""Tokens trained per second per chip: every token of every step in the
window, over the window's host-clock seconds and the chips."""


def read(run):
    return len(run.steps) * run.tokens_per_step / run.window_s / run.chips
