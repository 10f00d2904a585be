"""coordsim — deterministic in-process control-plane simulator.

Runs hundreds of :class:`horovod_tpu.coordination.Node` controller state
machines over virtual pipes with an injected clock — no sockets, no data
plane, no real time — so the lease/election/retry protocol is verified
by exhaustive assertion *before* it ever coordinates a real job:

* **Safety**: never two coordinators committing in one epoch, under
  every chaos kind ``faults.py`` can throw at the wire.
* **Shape**: per-tick fan-in at the busiest node stays O(log N) while
  the flat star's coordinator ingests O(N).
* **Liveness**: agreement converges within a bounded number of virtual
  ticks under message drop/dup/reorder/delay, host partitions and a
  coordinator crash mid-tick.

``python -m tools.coordsim --ranks 64 --chaos drop:0.1`` runs one
episode and prints the stats JSON; ``tests/test_coordsim.py`` is the CI
lane and holds the fan-in bound at N=256.
"""

from tools.coordsim.net import VirtualClock, VirtualNetwork
from tools.coordsim.sim import Simulation, hosts_for

__all__ = ["VirtualClock", "VirtualNetwork", "Simulation", "hosts_for"]
