"""Modelled carbon of a measured window on one TPU v5e chip.

Every constant here is modelled, not measured: the benchmark reads no power
meter. They are one quarter of the repository's four-chip TPU v5e server
(``repro.core.carbon.TPU_V5E_SPEC``), copied so that no later change to the
program moves the yardstick:

* busy power 286.25 W: one chip at 220 W (TPU_V5E_SPEC.gpu_power_max_w,
  4 x 220 W) plus a quarter of the host's CPU (225 W) and memory (40 W);
* idle power 126.25 W: one chip at 60 W (gpu_power_idle_w, 4 x 60 W) plus
  the same quarter of CPU and memory;
* embodied 27.525 kgCO2e over 5 years: a quarter of 70 kg (four v5e chips
  and board, ACT-style estimate), 9.3 kg (CPU) and 30.8 kg (512 GB DDR4),
  over TPU_V5E_SPEC.lifetime_years;
* grid intensity 33 gCO2e/kWh: France (``repro.core.carbon.GRID_CI["FR"]``,
  the paper's low-carbon grid).
"""

BUSY_W = 220.0 + (225.0 + 40.0) / 4
IDLE_W = 60.0 + (225.0 + 40.0) / 4
EMBODIED_G = (70.0 + 9.3 + 30.8) / 4 * 1000.0
LIFETIME_S = 5 * 365.25 * 24 * 3600
GRID_G_PER_KWH = 33.0
J_PER_KWH = 3.6e6


def window_carbon_g(busy_s: float, window_s: float) -> float:
    """Operational plus embodied gCO2e of ``window_s`` seconds of which
    ``busy_s`` were spent serving."""
    idle_s = max(window_s - busy_s, 0.0)
    joules = busy_s * BUSY_W + idle_s * IDLE_W
    return joules / J_PER_KWH * GRID_G_PER_KWH \
        + EMBODIED_G * window_s / LIFETIME_S
