"""Traffic kind ``train_per_chip``: the ``train`` kind's verified campaigns
on ``slots`` slots at once, one slot per chip.

The boards, the oracle, the accounting and the check are ``train``'s.
The farm gives each slot its own device, and a program compiles anew for
each device it runs on, so set-up warms one campaign on every slot (where
``train`` warms one campaign) and no slot compiles inside the window.
Each campaign's oracle replays on its own board's chip: the program's
``CommitStreamVerifier`` runs on the device it was built under, and the
farm builds a board's state, and so its verifier, under the slot's
device.
"""
from __future__ import annotations

from pathlib import Path

from chip.harness import kind_module

_train = kind_module("train", Path(__file__).resolve().parents[1])


class Kind(_train.Kind):
    def farm(self, rec, boards=None, **kw):
        if boards is not None:          # set-up's warm-up: one per slot
            boards = max(boards, int(self.mix["slots"]))
        return super().farm(rec, boards=boards, **kw)
