"""The paper's contribution: BSP accelerator model, pseudo-streams, hypersteps,
BSPS cost function, and the pod-level three-term roofline generalisation."""

from repro.core.bsp import (
    BSPAccelerator,
    BSPComputer,
    EPIPHANY_III,
    TPU_V5E_CHIP,
    TPU_V5E_POD,
)
from repro.core.cost import (
    HyperstepCost,
    SuperstepCost,
    bsp_cost,
    bsps_cost,
    cannon_bsp_cost,
    cannon_bsps_cost,
    cannon_hyperstep,
    cannon_k_equal,
    inner_product_cost,
)
from repro.core.faults import (
    FAULT_KINDS,
    FaultInjected,
    FaultInjector,
    FaultPlan,
    FaultRecord,
    FaultSpec,
    corrupt_array,
    fault_signature,
)
from repro.core.health import (
    HEALTH_CODES,
    HealthEvent,
    HealthMonitor,
)
from repro.core.hyperstep import (
    CompiledHyperstepProgram,
    HyperstepRecord,
    HyperstepRunner,
    run_bsps,
)
from repro.core.plan import (
    CompiledSchedule,
    PlanChoice,
    ScratchSpec,
    StreamPlan,
    TokenSpec,
    autotune,
    enumerate_plans,
    host_plan,
)
from repro.core.roofline import (
    PEAKS,
    TPU_V5E,
    HardwareSpec,
    RooflineReport,
    analyze,
    hardware_spec,
)
from repro.core.stream import Stream, StreamSet

__all__ = [
    "BSPAccelerator", "BSPComputer", "EPIPHANY_III", "TPU_V5E_CHIP", "TPU_V5E_POD",
    "HyperstepCost", "SuperstepCost", "bsp_cost", "bsps_cost",
    "cannon_bsp_cost", "cannon_bsps_cost", "cannon_hyperstep", "cannon_k_equal",
    "inner_product_cost",
    "FAULT_KINDS", "FaultInjected", "FaultInjector", "FaultPlan",
    "FaultRecord", "FaultSpec", "corrupt_array", "fault_signature",
    "HEALTH_CODES", "HealthEvent", "HealthMonitor",
    "CompiledHyperstepProgram", "HyperstepRecord", "HyperstepRunner", "run_bsps",
    "CompiledSchedule", "PlanChoice", "ScratchSpec", "StreamPlan", "TokenSpec",
    "autotune", "enumerate_plans", "host_plan",
    "PEAKS", "TPU_V5E", "HardwareSpec", "RooflineReport", "analyze",
    "hardware_spec",
    "Stream", "StreamSet",
]
