"""LGC core: layered gradient compression, FL loop, channels, control.

The modules below are bound together by the engine-equivalence ladder
(loop ~ batched == sharded History; docs/ARCHITECTURE.md §1) -- each
module's docstring names the invariant it participates in and the test
that enforces it."""
from .compressor import (LGCCompressor, flatten_tree, lgc_compress, lgc_layers,
                         lgc_compress_topk, lgc_compress_traced,
                         top_alpha_beta, top_k, tree_size, unflatten_like,
                         wire_bytes)
from .error_feedback import (EF_STORES, DenseEFStore, EFState, Int8EFStore,
                             ServerEFStore, ef_compress, init_ef,
                             make_ef_store)
from .channels import (DEFAULT_CHANNELS, ChannelSpec, DeviceProfile,
                       comm_cost, comp_cost, sample_channels)
from .fl import (ControllerFleet, FLConfig, FLTask, FixedController, History,
                 LGCSimulator, RoundDecision, run_baseline)
from .scenario import (SCENARIOS, DropoutSpec, GaussMarkovSpec,
                       GilbertElliottSpec, HeteroFleetSpec, Scenario,
                       StragglerSpec, get_scenario)
from .controller import (DDPGConfig, DDPGController, FleetDDPG,
                         decode_actions, make_ddpg_controllers,
                         make_fleet_ddpg, obs_dim)
from .audit import audit_simulator, recompute_spend
from .population import (COHORT_SAMPLERS, Population, make_population,
                         make_population_task, run_population, sample_cohort)
from .server import (AGGREGATORS, AggregatorSpec, ServerState, get_aggregator,
                     init_server_state, window_deadline)
from .convergence import ProblemConstants, corollary1_rate, theorem1_bound

__all__ = [
    "LGCCompressor", "flatten_tree", "lgc_compress", "lgc_layers",
    "lgc_compress_topk", "lgc_compress_traced",
    "top_alpha_beta", "top_k", "tree_size", "unflatten_like", "wire_bytes",
    "EF_STORES", "DenseEFStore", "EFState", "Int8EFStore", "ServerEFStore",
    "ef_compress", "init_ef", "make_ef_store",
    "DEFAULT_CHANNELS", "ChannelSpec", "DeviceProfile", "comm_cost",
    "comp_cost", "sample_channels",
    "ControllerFleet", "FLConfig", "FLTask", "FixedController", "History",
    "LGCSimulator", "RoundDecision", "run_baseline",
    "SCENARIOS", "DropoutSpec", "GaussMarkovSpec", "GilbertElliottSpec",
    "HeteroFleetSpec", "Scenario", "StragglerSpec", "get_scenario",
    "DDPGConfig", "DDPGController", "FleetDDPG", "decode_actions",
    "make_ddpg_controllers", "make_fleet_ddpg", "obs_dim",
    "audit_simulator", "recompute_spend",
    "ProblemConstants", "corollary1_rate", "theorem1_bound",
    "COHORT_SAMPLERS", "Population", "make_population",
    "make_population_task", "run_population", "sample_cohort",
    "AGGREGATORS", "AggregatorSpec", "ServerState", "get_aggregator",
    "init_server_state", "window_deadline",
]
