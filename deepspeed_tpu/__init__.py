"""deepspeed_tpu — a TPU-native distributed training & inference framework.

Capability surface of DeepSpeed 0.9.1 (reference ``deepspeed/__init__.py``), designed
TPU-first: sharding specs + XLA collectives over a named ``jax.sharding.Mesh`` instead
of NCCL hook machinery, Pallas kernels instead of CUDA extensions.

Public API (mirrors reference ``deepspeed/__init__.py:54,:251``):
    initialize()       -> (engine, optimizer, dataloader, lr_scheduler)
    init_inference()   -> InferenceEngine
    init_distributed() -> multi-host rendezvous
"""

__version__ = "0.1.0"
__git_branch__ = "main"

from . import comm  # noqa: F401
from . import pipe  # noqa: F401
from . import zero  # noqa: F401
from .accelerator import get_accelerator, set_accelerator  # noqa: F401
from .config import DeepSpeedConfig, load_config  # noqa: F401
from .comm.comm import init_distributed  # noqa: F401
from .utils import OnDevice  # noqa: F401  (reference deepspeed.OnDevice)


def initialize(args=None, model=None, optimizer=None, model_parameters=None,
               training_data=None, lr_scheduler=None, mesh=None, dist_init_required=None,
               collate_fn=None, config=None, config_params=None):
    """Build a training engine (reference ``deepspeed/__init__.py:54``).

    Returns ``(engine, optimizer, training_dataloader, lr_scheduler)``.
    ``mesh`` replaces the reference's ``mpu`` argument: pass a prebuilt
    ``jax.sharding.Mesh`` or let the config's ``mesh`` section build one.
    """
    from .runtime.engine import DeepSpeedEngine

    config = config if config is not None else config_params
    if config is None and args is not None:
        config = getattr(args, "deepspeed_config", None)
    if config is None:
        raise ValueError("deepspeed_tpu.initialize requires a config (dict or JSON path)")

    if dist_init_required or dist_init_required is None:
        init_distributed()

    # engine class choice (reference deepspeed/__init__.py:141-181): the hybrid
    # (RLHF) engine when configured, else the plain training engine (pipeline
    # scheduling lives inside the engine here, not in a subclass).
    engine_cls = DeepSpeedEngine
    config = load_config(config)  # parse once; the engine accepts the instance
    if config.hybrid_engine.enabled:
        from .runtime.hybrid_engine import DeepSpeedHybridEngine

        engine_cls = DeepSpeedHybridEngine

    engine = engine_cls(
        model=model,
        optimizer=optimizer,
        model_parameters=model_parameters,
        training_data=training_data,
        lr_scheduler=lr_scheduler,
        mesh=mesh,
        collate_fn=collate_fn,
        config=config,
    )
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def init_inference(model=None, config=None, **kwargs):
    """Build an inference engine (reference ``deepspeed/__init__.py:251``).

    ``model`` may be a live zoo model OR a path to a HuggingFace checkpoint
    directory (the reference's ``init_inference(model, checkpoint=...)`` +
    module_inject flow): the checkpoint is mapped into the zoo's pytree and
    served with auto-TP placement (``module_inject/hf.py``).
    """
    from .inference.engine import InferenceEngine
    from .inference.config import DeepSpeedInferenceConfig

    if isinstance(config, DeepSpeedInferenceConfig):
        ds_config = config
    else:
        merged = dict(config or {})
        merged.update(kwargs)
        ds_config = DeepSpeedInferenceConfig.from_dict(merged)

    if isinstance(model, str):
        import jax

        from .module_inject import hf_model_from_pretrained
        from .models.layers import split_params_axes

        model, values = hf_model_from_pretrained(model)
        axes = split_params_axes(
            jax.eval_shape(model.init, jax.random.PRNGKey(0)))[1]
        return InferenceEngine(model, ds_config, model_parameters=(values, axes))
    return InferenceEngine(model, ds_config)


def add_config_arguments(parser):
    """Reference ``deepspeed/__init__.py:228``: add --deepspeed/--deepspeed_config."""
    group = parser.add_argument_group("DeepSpeed-TPU", "DeepSpeed-TPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed-TPU (helper flag for config scripts)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to the DeepSpeed-TPU JSON config")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help=argparse_suppress())
    return parser


def argparse_suppress():
    import argparse

    return argparse.SUPPRESS
