"""Experiment management + checkpointing (reference layer:
/root/reference/utils/harness_utils.py + torch.save plumbing)."""

from .checkpoint import (
    MID_LEVEL,
    MODEL_INIT,
    MODEL_REWIND,
    OPTIMIZER_INIT,
    OPTIMIZER_REWIND,
    ExperimentCheckpoints,
    pack_mask_tree,
    reset_weights,
    restore_model_tree,
    restore_pytree,
    rewind_roles,
    save_model_tree,
    save_pytree,
    unpack_mask_tree,
)
from .experiment import (
    MetricsLogger,
    config_fingerprint,
    display_training_info,
    expt_prefix,
    gen_expt_dir,
    resume_experiment,
    save_config,
    set_seed,
)

__all__ = [
    "ExperimentCheckpoints",
    "reset_weights",
    "rewind_roles",
    "save_pytree",
    "restore_pytree",
    "save_model_tree",
    "restore_model_tree",
    "pack_mask_tree",
    "unpack_mask_tree",
    "MID_LEVEL",
    "MODEL_INIT",
    "MODEL_REWIND",
    "OPTIMIZER_INIT",
    "OPTIMIZER_REWIND",
    "MetricsLogger",
    "config_fingerprint",
    "gen_expt_dir",
    "resume_experiment",
    "expt_prefix",
    "save_config",
    "set_seed",
    "display_training_info",
]
