"""Discrete-event fleet simulator: scaling policy as testable code.

No TPUs, no real sleeps — a virtual clock (``sim/core.py``) drives
arrival traces (``sim/traces.py``: diurnal, bursty MMPP, heavy-tail
lengths) through modeled workers (``sim/worker.py``, assumed service-time
defaults) while the REAL Planner and AdmissionController run
against it in driven mode, and PR-5 ``FaultPlan``s compose in at
simulated timestamps (``sim/faults.py``). See docs/autoscaling.md.
"""

from dynamo_tpu.sim.core import SimClock, SimLoop, drive
from dynamo_tpu.sim.faults import SimFaultDriver
from dynamo_tpu.sim.fleet import FleetSim, SimConfig, SimConnector
from dynamo_tpu.sim.traces import (
    LengthModel,
    SimRequest,
    bursty_trace,
    diurnal_trace,
    merge_traces,
    poisson_trace,
)
from dynamo_tpu.sim.worker import SimWorker, WorkerProfile

__all__ = [
    "SimClock", "SimLoop", "drive",
    "SimFaultDriver",
    "FleetSim", "SimConfig", "SimConnector",
    "LengthModel", "SimRequest", "bursty_trace", "diurnal_trace",
    "merge_traces", "poisson_trace",
    "SimWorker", "WorkerProfile",
]
