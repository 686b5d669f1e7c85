"""The work a window cannot do without, counted from what it produced.

The count reads the spikes that were actually fired and the neurons that
are alive, never the padded shapes or packet bounds of an implementation,
so it stays the same whatever implements the window:

* per spike, every outgoing synapse's target (4 B), weight (4 B) and delay
  (1 B) are read once, and its ring slot is read and written (4 B + 4 B);
  one addition per synapse;
* per alive neuron and cycle, the neuron state (``v``, ``i_syn``,
  ``refrac``: 12 B) is read and written, its ring slot is read and cleared
  (4 B + 4 B), and its spike bit is written out (1 B); about ten
  operations (two propagator products, the drive, the threshold).
"""

from __future__ import annotations

import numpy as np

SYNAPSE_BYTES = 4 + 4 + 1 + 4 + 4
NEURON_CYCLE_BYTES = 2 * 12 + 4 + 4 + 1
SYNAPSE_FLOPS = 1
NEURON_CYCLE_FLOPS = 10


def necessary_work(raster: np.ndarray, out_degree: np.ndarray,
                   n_alive: int) -> dict:
    """Bytes and operations a window needs.

    ``raster`` is ``[cycles, n_rows]`` bool (the window's spikes, padded
    ids), ``out_degree`` the number of outgoing synapses of each padded
    row, ``n_alive`` the live neurons.
    """
    cycles = raster.shape[0]
    synapses = int(out_degree[np.nonzero(raster)[1]].sum())
    neuron_cycles = int(n_alive) * cycles
    return {
        "spikes": int(raster.sum()),
        "synapses": synapses,
        "neuron_cycles": neuron_cycles,
        "bytes": synapses * SYNAPSE_BYTES + neuron_cycles * NEURON_CYCLE_BYTES,
        "flops": synapses * SYNAPSE_FLOPS + neuron_cycles * NEURON_CYCLE_FLOPS,
    }


def least_time_s(work: dict, peak: dict) -> tuple[float, str]:
    """The least time the chip needs for ``work`` and which peak bounds it.

    ``peak`` has ``hbm_bytes_per_s`` and ``flops_per_s``.
    """
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    t_flops = work["flops"] / peak["flops_per_s"]
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "flops")
