"""Plain reference for the CartPole-v1 step: the published equations of
Barto, Sutton and Anderson with gymnasium's constants and Euler integration,
in float64 numpy. Imports nothing from the program.

TOLERANCE 1e-5 (absolute, on state components of magnitude up to ~3 and
accelerations up to ~20 x dt): the program computes in float32, whose
rounding through the ~20 operations of a step stays under 1e-5 at these
magnitudes. A wrong constant, sign or integration order moves the next
state by 1e-3 or more and fails.
"""

from __future__ import annotations

import numpy as np

STATE_TOL = 1e-5

GRAVITY, MASSCART, MASSPOLE, LENGTH = 9.8, 1.0, 0.1, 0.5
FORCE_MAG, DT = 10.0, 0.02
X_LIMIT, THETA_LIMIT = 2.4, 12 * np.pi / 180


def step(state: np.ndarray, action: np.ndarray):
    """state: [N, 4] (x, x_dot, theta, theta_dot); action: [N] in {0, 1}.
    Returns (next state [N, 4], terminated [N])."""
    x, x_dot, theta, theta_dot = np.asarray(state, np.float64).T
    total_mass = MASSCART + MASSPOLE
    polemass_length = MASSPOLE * LENGTH
    force = np.where(np.asarray(action) == 1, FORCE_MAG, -FORCE_MAG)
    cos, sin = np.cos(theta), np.sin(theta)
    temp = (force + polemass_length * theta_dot ** 2 * sin) / total_mass
    theta_acc = (GRAVITY * sin - cos * temp) / (
        LENGTH * (4.0 / 3.0 - MASSPOLE * cos ** 2 / total_mass))
    x_acc = temp - polemass_length * theta_acc * cos / total_mass
    nxt = np.stack([x + DT * x_dot, x_dot + DT * x_acc,
                    theta + DT * theta_dot, theta_dot + DT * theta_acc], axis=1)
    terminated = (np.abs(nxt[:, 0]) > X_LIMIT) | (np.abs(nxt[:, 2]) > THETA_LIMIT)
    return nxt, terminated
