"""HPO workloads: jittable objectives for the batched evaluation path."""

from hpbandster_tpu.workloads.toys import (  # noqa: F401
    BRANIN_OPT,
    HARTMANN6_OPT,
    branin_dict,
    branin_from_vector,
    branin_space,
    hartmann6_from_vector,
    hartmann6_space,
)
from hpbandster_tpu.workloads.cnn import (  # noqa: F401
    CNN_TARGET_VAL_ACCURACY,
    CNNConfig,
    cnn_forward,
    cnn_space,
    decode_cnn_hparams,
    init_cnn_params,
    make_cnn_accuracy_fn,
    make_cnn_error_fn,
    make_cnn_eval_fn,
    make_image_dataset,
)
from hpbandster_tpu.workloads.resnet import (  # noqa: F401
    ResNetConfig,
    decode_resnet_hparams,
    init_resnet_params,
    make_resnet_eval_fn,
    resnet_forward,
    resnet_space,
)
from hpbandster_tpu.workloads.ensemble import (  # noqa: F401
    EnsembleState,
    ensemble_lane_bytes,
    make_mlp_ensemble,
    make_uninterrupted_train_fn,
    shard_ensemble_state,
)
from hpbandster_tpu.workloads.mlp import (  # noqa: F401
    MLPConfig,
    batched_sgd_train_step,
    sgd_train_step_batch,
    decode_mlp_hparams,
    init_mlp_params,
    make_mlp_eval_fn,
    make_synthetic_dataset,
    mlp_forward,
    mlp_space,
)
from hpbandster_tpu.workloads.transformer import (  # noqa: F401
    TRANSFORMER_TARGET_VAL_ACCURACY,
    TransformerConfig,
    make_copy_dataset,
    make_transformer_accuracy_fn,
    make_transformer_error_fn,
    make_transformer_eval_fn,
    transformer_forward,
    transformer_forward_seq_parallel,
    transformer_space,
)
from hpbandster_tpu.workloads.teacher import (  # noqa: F401
    TARGET_VAL_ACCURACY,
    TeacherConfig,
    make_teacher_accuracy_fn,
    make_teacher_dataset,
    make_teacher_eval_fn,
    teacher_space,
)
from hpbandster_tpu.workloads.kimi_linear import (  # noqa: F401
    KimiLinearConfig,
    kimi_linear_space,
    make_kimi_linear_eval_fn,
)
from hpbandster_tpu.workloads.mellum2 import (  # noqa: F401
    Mellum2Config,
    make_mellum2_eval_fn,
    mellum2_space,
)
from hpbandster_tpu.workloads.ouro import (  # noqa: F401
    OuroConfig,
    init_ouro_params,
    make_ouro_eval_fn,
    ouro_lane_bytes,
    ouro_space,
)
from hpbandster_tpu.workloads.lfm2 import (  # noqa: F401
    Lfm2Config,
    init_lfm2_params,
    lfm2_forward,
    lfm2_lane_bytes,
    lfm2_loss,
    lfm2_space,
    make_lfm2_eval_fn,
)
from hpbandster_tpu.workloads.sdar import (  # noqa: F401
    SdarConfig,
    init_sdar_params,
    make_diffusion_dataset,
    make_sdar_eval_fn,
    sdar_forward,
    sdar_lane_bytes,
    sdar_loss,
    sdar_space,
)
from hpbandster_tpu.workloads.delta_rule import delta_rule_chunked  # noqa: F401
from hpbandster_tpu.workloads.olmo_hybrid import (  # noqa: F401
    OlmoHybridConfig,
    init_olmo_hybrid_params,
    make_olmo_hybrid_eval_fn,
    olmo_hybrid_forward,
    olmo_hybrid_lane_bytes,
    olmo_hybrid_loss,
    olmo_hybrid_space,
)
from hpbandster_tpu.workloads.laguna import (  # noqa: F401
    LagunaConfig,
    init_laguna_params,
    laguna_forward,
    laguna_lane_bytes,
    laguna_loss,
    laguna_space,
    make_laguna_eval_fn,
)
