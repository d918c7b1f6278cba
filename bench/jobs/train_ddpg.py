"""Training job of the DDPG cells: the fleet jobs of ``train.py``, with the
agent's select compared over the first ``check.select_epochs`` epochs.

At the chip's default matrix-product precision the program's actor and
critic part from the reference's (float32 at ``highest`` precision) by
round-off, and the actor-critic feeds that back into every later update.
Over a 300-epoch job the reference's nets drift far enough from the
program's that near-tied rows reorder, its K nearest assignments are other
ones, and the select's ``q_gap_mean`` and ``d_gap_mean`` measure that
drift and not the select: a sound job then reads as high as a wrong
answer (on the CPU, whose products are float32, both stay under 1e-6 over
the whole job).  So those two are taken over the first epochs, while the
nets still track; the env step (``lat_gap``, ``moved_mismatch``), the
feasibility of every choice (``infeasible``) and the update
(``update_gap``, the online nets' change over the whole job) are compared
as ``train.py`` compares them.
"""
from __future__ import annotations

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import train      # noqa: E402  (puts bench/ on the path)
import reference  # noqa: E402


class Job(train.Job):
    def check(self) -> list:
        """``train.Job.check``, with the reference's per-epoch Q and
        distance gaps cut to the first ``select_epochs`` epochs."""
        full, first = reference.rollout, self.cell["check"]["select_epochs"]

        def rollout(*args):
            (lat, moved, q_gap, d_gap, bad), st, g0 = full(*args)
            return (lat, moved, q_gap[:first], d_gap[:first], bad), st, g0
        reference.rollout = rollout
        try:
            return super().check()
        finally:
            reference.rollout = full
