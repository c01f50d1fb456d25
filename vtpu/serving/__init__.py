"""TPU-native serving engine: continuous batching over a slot-based KV cache."""

from vtpu.models.slots import (
    batched_decode_step,
    prefill_into_slot,
    prefill_into_slots,
)
from vtpu.serving.disagg import DisaggConfig
from vtpu.serving.engine import (
    BlockAllocator,
    Request,
    ServingConfig,
    ServingEngine,
    Status,
    Terminal,
    WaitQueue,
)
from vtpu.serving.fabric import (
    EngineHost,
    HostClient,
    RemoteEngine,
    TransportError,
    connect_host,
    loopback_pair,
    spawn_host,
    tcp_connect,
)
from vtpu.serving.faults import EngineDeath, FaultPlan, FaultSpec
from vtpu.serving.fleet import (
    EngineFleet,
    FleetConfig,
    LeastPressureRoutePolicy,
    RoutePolicy,
    load_route_policy,
)
from vtpu.serving.migrate import MigrationError, drain_engine, migrate
from vtpu.serving.shed import (
    EngineSignals,
    PriorityDeadlineShedPolicy,
    ShedPolicy,
)

__all__ = [
    "BlockAllocator",
    "DisaggConfig",
    "EngineDeath",
    "EngineFleet",
    "EngineHost",
    "EngineSignals",
    "FaultPlan",
    "FaultSpec",
    "FleetConfig",
    "HostClient",
    "LeastPressureRoutePolicy",
    "MigrationError",
    "PriorityDeadlineShedPolicy",
    "RemoteEngine",
    "Request",
    "RoutePolicy",
    "ServingConfig",
    "ServingEngine",
    "ShedPolicy",
    "Status",
    "Terminal",
    "TransportError",
    "WaitQueue",
    "batched_decode_step",
    "connect_host",
    "drain_engine",
    "load_route_policy",
    "loopback_pair",
    "migrate",
    "prefill_into_slot",
    "prefill_into_slots",
    "spawn_host",
    "tcp_connect",
]
