"""Simulated DRAM commands of every job completed in the window, over the
window's host seconds. The commands are counted from the cell's reference
command lists, never from the program's counters."""


def value(run):
    return run.commands / run.seconds if run.jobs else None
