from repro.federated.algorithms import (  # noqa: F401
    FLAlgorithm,
    ServerState,
    make_algorithm,
    make_local_update,
    server_init,
    server_optimizer_step,
    server_state_from_tree,
)
from repro.federated.telemetry import (  # noqa: F401
    Telemetry,
    get_telemetry,
    set_telemetry,
)
from repro.federated.dist import (  # noqa: F401
    DistConfig,
    DistContext,
    dist_jit,
    two_stage_psum,
)
from repro.federated.engine import (  # noqa: F401
    AccumulationEngine,
    EngineConfig,
    EngineStats,
    aggregate,
    shard_stats,
)
from repro.federated.round_engine import (  # noqa: F401
    ReferenceLoop,
    RoundConfig,
    RoundEngine,
)
from repro.federated.async_engine import (  # noqa: F401
    AsyncConfig,
    AsyncRoundEngine,
    AsyncState,
    ClientHealth,
    run_adaptive_rounds,
    run_chaos_timeline,
)
from repro.federated.streaming_engine import (  # noqa: F401
    StreamConfig,
    StreamState,
    StreamingEngine,
    WaveTrace,
)
from repro.federated.personalization import (  # noqa: F401
    PersonalizationEngine,
    PersonalizeConfig,
    PersonalizedHeads,
    ReferencePersonalizedLoop,
)
from repro.federated import arrivals  # noqa: F401
from repro.federated.sampling import ClientSampler, sample_round  # noqa: F401
from repro.federated.simulator import FLTask, run_federated  # noqa: F401
from repro.federated.fed3r_driver import (  # noqa: F401
    run_fed3r,
    run_fed3r_ft,
    run_fedncm,
)
from repro.federated import costs  # noqa: F401
