"""On a TPU, run the nemotron3_nano_30b_a3b_train cell's program for a
number of steps and make the comparison that decides its `correct` against
the adapter's exact reference, its all-bfloat16 one and every wrong model
of `nemotron_h_lm.DEPARTURES` (the state carried in bfloat16, dt and dt A
in bfloat16, no D skip, the norm before the gate, one group of 4096 in the
gated norm, relu in place of relu^2, weights from s + b, no 2.5, rotary on,
head j reading group j mod 8) on the SAME weights: the exact one has to
pass, every other to fail at least one limit.  tools/kanana2_departures.py
with this cell as its default (the loop is that file's; it knows no cell);
its reference runs the Mamba-2 scan token by token on the host, about a
minute a reference at 6,144 tokens.

    python tools/nemotron_h_departures.py --seed 2481300071 --steps 120

(`--rehearse`: the cell's rehearsal sizes on the CPU, proves the plumbing.)
Prints one JSON line a step count; PERF.md (PR 57) keeps what it read.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from tools import kanana2_departures  # noqa: E402

if __name__ == "__main__":
    if "--cell" not in sys.argv:
        sys.argv[1:1] = ["--cell", "nemotron3_nano_30b_a3b_train"]
    sys.exit(kanana2_departures.main())
