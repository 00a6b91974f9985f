"""Phases per MoE layer of the schedule table that the window's steps
ran: the matchings the planner needed for the realized rank-to-rank
demand (the paper's count; fewer is better)."""


def read(run):
    return run.phases
