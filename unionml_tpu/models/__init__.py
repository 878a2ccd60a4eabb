"""Model library: flagship flax models for the benchmark configs (BASELINE.json)."""

from unionml_tpu.models.afmoe import AfmoeConfig, AfmoeTransformer, afmoe_partition_rules  # noqa: F401
from unionml_tpu.models.bailing_hybrid import (  # noqa: F401
    BailingHybridConfig,
    BailingHybridTransformer,
    bailing_hybrid_partition_rules,
)
from unionml_tpu.models.bert import BertConfig, BertEncoder, bert_partition_rules, classification_loss  # noqa: F401
from unionml_tpu.models.generate import (  # noqa: F401
    DraftSpec,
    GenerationConfig,
    Generator,
    PrefixCache,
    init_cache,
    sample_tokens,
)
from unionml_tpu.models.glm4_moe_lite import (  # noqa: F401
    Glm4MoeLiteConfig,
    Glm4MoeLiteTransformer,
    glm4_moe_lite_partition_rules,
)
from unionml_tpu.models.speculative import SpeculativeGenerator  # noqa: F401
from unionml_tpu.models.structured import (  # noqa: F401
    ConstraintSet,
    TokenConstraint,
    compile_regex,
    json_object,
    literal_choice,
    stop_sequences,
    vocab_from_tokenizer,
)
from unionml_tpu.models.llama import (  # noqa: F401
    Llama,
    LlamaConfig,
    causal_lm_loss,
    chunked_causal_lm_loss,
    llama_partition_rules,
    lora_optimizer,
    lora_param_labels,
)
from unionml_tpu.models.mlp import MLPClassifier, MLPConfig  # noqa: F401
from unionml_tpu.models.moe import (  # noqa: F401
    ExpertShare,
    MoEConfig,
    MoELayer,
    MoETransformer,
    moe_lm_loss,
    moe_partition_rules,
    top_k_dispatch,
)
from unionml_tpu.models.vit import (  # noqa: F401
    PipelinedViT,
    ViT,
    ViTConfig,
    pipelined_vit_partition_rules,
    vit_partition_rules,
)
