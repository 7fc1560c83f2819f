from .alexnet import build_alexnet
from .candle_uno import build_candle_uno
from .dlrm import build_dlrm, build_xdl
from .inception import build_inception_v3
from .laguna import build_laguna
from .mlp import build_mlp_unify
from .moe import build_moe_encoder, build_moe_mlp
from .nmt import build_nmt
from .resnet import build_resnet50, build_resnext50
from .transformer import build_bert, build_gpt, build_transformer

#: the builders that record a `DecoderRecipe` on the model they build
#: (what a decode twin is built from: `decoding.decoder_recipe`), as
#: `<module>.<function>` under this package; each module imports
#: `..decoding`, so they are named here and imported where they are used
SERVED_BUILDERS = (
    "transformer.build_gpt", "kimi_k2.build_kimi_k2",
    "qwen3_next.build_qwen3_next", "ouro.build_ouro",
    "longcat_flash.build_longcat_flash", "evabyte.build_evabyte",
    "laguna.build_laguna", "glm_dsa.build_glm_dsa",
    "granite_hybrid.build_granite_hybrid")
