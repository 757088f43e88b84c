"""Model stack: config-driven text encoders, the denoiser (a conditional
U-Net or a transformer over patch tokens), and the VAE.

TPU-first re-design of the model surface the reference borrows from
diffusers 0.8.1 (`/root/reference/requirements.txt:1`): pure-functional
modules over explicit param pytrees, NHWC layouts, static attention layouts
derived from config (no runtime monkey-patching), fused attention everywhere
the prompt-to-prompt controller provably never looks.
"""

from .config import (
    LDM256,
    PIXART_SIGMA,
    TINY_DIT,
    TINY_LDM,
    TINY_V,
    SD14_HR,
    SD21,
    SD21_BASE,
    SD14,
    SDXL,
    TINY,
    TINY_XL,
    PipelineConfig,
    TextEncoderConfig,
    TransformerConfig,
    UNetConfig,
    VAEConfig,
    denoiser_layout,
    transformer_attn_specs,
    transformer_layout,
    unet_attn_specs,
    unet_layout,
)
from .conditioning import Conditioning
from .text_encoder import apply_text_encoder, init_text_encoder
from .unet import apply_unet, init_unet
from .transformer import apply_transformer, init_transformer
from . import vae

__all__ = [
    "LDM256", "PIXART_SIGMA", "SD14", "SD14_HR", "SD21", "SD21_BASE", "SDXL",
    "TINY", "TINY_DIT", "TINY_LDM", "TINY_V", "TINY_XL", "Conditioning",
    "PipelineConfig", "TextEncoderConfig", "TransformerConfig", "UNetConfig",
    "VAEConfig", "denoiser_layout", "transformer_attn_specs",
    "transformer_layout", "unet_attn_specs", "unet_layout",
    "apply_text_encoder", "init_text_encoder",
    "apply_unet", "init_unet", "apply_transformer", "init_transformer",
    "vae",
]
