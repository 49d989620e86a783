"""Lanes of the paper's figure x MPL grid, for back-to-back grid calls.

Every (figure, MPL) point runs ``replicas_per_point`` lanes per call.
Each lane of call ``c`` in a run with seed ``s`` has its own lane seed,
drawn from ``(s, c)``: lanes that shared a seed would share their
random streams, and sums over the grid would then average far fewer
independent runs than there are lanes.  Lanes are figure-major: lane
``f*M*R + m*R + r`` is figure ``figures[f]`` at ``mpl[m]``, replica
``r``.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np


def lanes(config: dict, traffic: dict, seed: int, call: int
          ) -> List[Tuple[int, int, int]]:
    """(figure, MPL, lane seed) of every lane of call ``call``; lane
    seeds are non-negative int32."""
    points = [(f, m) for f in traffic["figures"] for m in config["mpl"]]
    reps = int(traffic["replicas_per_point"])
    state = np.random.SeedSequence([seed, call]).generate_state(
        len(points) * reps)
    return [(f, m, int(state[i * reps + r]) & 0x7FFFFFFF)
            for i, (f, m) in enumerate(points) for r in range(reps)]


def lane_params(config: dict, fig: int, mpl: int, seed: int,
                horizon: float) -> dict:
    """The Table 1 settings of one lane, keyed as the configuration
    file keys them."""
    return dict(config["table1"], **config["figures"][str(fig)],
                mpl=mpl, horizon=horizon, seed=seed)
