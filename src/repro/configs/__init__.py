"""Architecture config registry.

Every assigned architecture (plus the paper's own foundation-model stand-ins)
is selectable by id, e.g. ``--arch grok-1-314b``.
"""
from repro.models.config import ModelConfig

from repro.configs.grok_1_314b import CONFIG as _grok
from repro.configs.granite_34b import CONFIG as _granite34
from repro.configs.nemotron_4_340b import CONFIG as _nemotron
from repro.configs.yi_34b import CONFIG as _yi
from repro.configs.rwkv6_3b import CONFIG as _rwkv
from repro.configs.granite_3_2b import CONFIG as _granite2
from repro.configs.granite_moe_3b_a800m import CONFIG as _granitemoe
from repro.configs.zamba2_7b import CONFIG as _zamba
from repro.configs.granite_4_h_micro import CONFIG as _granite4h
from repro.configs.hubert_xlarge import CONFIG as _hubert
from repro.configs.pixtral_12b import CONFIG as _pixtral

ARCHS: dict[str, ModelConfig] = {
    c.name: c
    for c in [
        _grok, _granite34, _nemotron, _yi, _rwkv,
        _granite2, _granitemoe, _zamba, _hubert, _pixtral, _granite4h,
    ]
}

# The paper's own feature extractors (ResNet-50 / ViT-B / CLIP ViT-B/32) are
# stood in by a small encoder config usable on CPU — see DESIGN.md §6.
FOUNDATION_STANDIN = ModelConfig(
    name="foundation-standin",
    family="encoder",
    n_layers=4,
    d_model=256,
    n_heads=4,
    n_kv_heads=4,
    d_ff=512,
    vocab_size=64,
    mlp_variant="gelu",
    causal=False,
    frame_embed_dim=64,
)


def get_config(name: str) -> ModelConfig:
    if name == "foundation-standin":
        return FOUNDATION_STANDIN
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCHS)}")
    return ARCHS[name]
