"""The paper's primary contribution: KL-DRO robust decentralized SGD.

robust.py     — the KL-regularized DRO objective and the exp(l/mu)/mu scale
consensus.py  — the four mixer factories (dense / gossip / hub / identity,
                from repro.comm.mixers) and RepeatMixer, behind the uniform
                stateful Mixer protocol (repro.comm.protocol)
drdsgd.py     — DR-DSGD & DSGD train-step builders over node-stacked pytrees
api.py        — DecentralizedTrainer high-level API (step + scan-based run)
spec.py       — TrainerSpec: declarative construction shared by CLI/benchmarks
"""

from repro.comm import (
    CommMetrics,
    CommState,
    CompressionConfig,
    Mixer,
    ScheduleConfig,
)
from repro.core.robust import (
    RobustConfig,
    robust_scale,
    robust_objective,
    mixture_weights,
)
from repro.core.consensus import (
    RepeatMixer,
    make_dense_mixer,
    make_gossip_mixer,
    make_hub_mixer,
    make_identity_mixer,
    repeat_mixer,
)
from repro.core.drdsgd import (
    DecentralizedState,
    TrainStepConfig,
    build_train_step,
    build_eval_step,
    init_state,
    replicate_params,
)
from repro.core.api import DecentralizedTrainer, run_segments
from repro.core.spec import (
    TrainerSpec,
    add_compression_cli_args,
    add_dynamics_cli_args,
    add_obs_cli_args,
    compression_from_args,
)

__all__ = [
    "CommMetrics", "CommState", "CompressionConfig", "ScheduleConfig",
    "RobustConfig", "robust_scale", "robust_objective", "mixture_weights",
    "Mixer", "RepeatMixer",
    "make_dense_mixer", "make_gossip_mixer", "make_hub_mixer",
    "make_identity_mixer", "repeat_mixer",
    "DecentralizedState", "TrainStepConfig", "build_train_step",
    "build_eval_step", "init_state", "replicate_params",
    "DecentralizedTrainer", "run_segments",
    "TrainerSpec", "add_compression_cli_args", "add_dynamics_cli_args",
    "add_obs_cli_args", "compression_from_args",
]
