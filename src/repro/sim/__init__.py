"""Event-driven asynchronous fleet simulator.

Advances a simulated wall clock over an arbitrarily large client fleet:
latency profiles, the completion-event state and its pop, which the
async engine (``repro.engine.async_engine``) steps. The paper's load
metric (Var[X], AoI) is measured here in *simulated seconds* rather than
round index, which is where its fairness and no-coordination claims
become systems claims: stragglers, dropouts, and availability windows all
shift the realized selection process.
"""
from repro.sim.arrivals import (  # noqa: F401
    ArrivalProcess,
    sample_arrival_counts,
    sample_gen_lens,
    sample_requests,
)
from repro.sim.latency import (  # noqa: F401
    PROFILES,
    LatencyProfile,
    client_speed,
    get_profile,
    sample_avail_gap,
    sample_dropout,
    sample_latency,
)
from repro.sim.events import (  # noqa: F401
    init_event_state,
    next_k_events,
    schedule_completions,
)
