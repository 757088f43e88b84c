"""Model configurations and static attention-layout derivation.

The reference discovers its attention structure by walking the live U-Net and
counting hooked modules at registration time (`/root/reference/ptp_utils.py:223-242`).
Here the structure is a pure function of the config: :func:`unet_attn_specs`
enumerates every attention call site (place, kind, resolution, heads, key
length) in exact call order, and feeds `controllers.base.build_layout` — so
layer bookkeeping is settled before tracing and costs nothing at runtime.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

from ..controllers.base import AttnLayout, StoreConfig, build_layout


@dataclasses.dataclass(frozen=True)
class UNetConfig:
    """Shape config for the conditional U-Net (diffusers
    `UNet2DConditionModel` topology, e.g. SD-v1.4's 32 attention sites)."""

    sample_size: int = 64                  # latent side length
    in_channels: int = 4
    out_channels: int = 4
    block_channels: Tuple[int, ...] = (320, 640, 1280, 1280)
    # True → the down/up block at this level carries transformer blocks.
    attn_levels: Tuple[bool, ...] = (True, True, True, False)
    layers_per_block: int = 2
    num_heads: int = 8
    # When set, heads vary per level as channels // head_dim (LDM's fixed
    # per-head width); when None, num_heads applies uniformly (SD).
    head_dim: Optional[int] = None
    context_dim: int = 768                 # text-encoder hidden size
    context_len: int = 77
    # Transformer blocks per attention site group: one int for every
    # attentive level and the mid block, or one int a level (0: no transformer
    # there, whatever ``attn_levels`` says; the mid block takes the last).
    transformer_depth: Union[int, Tuple[int, ...]] = 1
    groups: int = 32
    ff_mult: int = 4
    freq_dim: Optional[int] = None         # sinusoidal dim; default block_channels[0]
    # Width of the vector embedded beside the time step (diffusers'
    # ``addition_embed_type="text_time"``): the pooled text, then each of
    # ``addition_sizes`` (the image's original height and width, the crop's
    # top and left, the target height and width) embedded
    # ``addition_time_dim`` wide. None: no such embedding.
    addition_embed_in: Optional[int] = None
    addition_time_dim: int = 256
    addition_sizes: Tuple[int, ...] = ()
    # The dtype kernels are stored in (leaves that are only ever an operand
    # of a product or convolution); biases and norm parameters stay float32.
    kernel_dtype: str = "float32"

    @property
    def time_embed_dim(self) -> int:
        return self.block_channels[0] * 4

    @property
    def levels(self) -> int:
        return len(self.block_channels)

    def resolution_at(self, level: int) -> int:
        return self.sample_size >> level

    def depth_at(self, level: int) -> int:
        """Transformer blocks of one site group at ``level``; 0 where the
        level has none."""
        if not self.attn_levels[level]:
            return 0
        depth = self.transformer_depth
        return depth if isinstance(depth, int) else depth[level]

    @property
    def mid_depth(self) -> int:
        depth = self.transformer_depth
        return depth if isinstance(depth, int) else depth[-1]

    def heads_for(self, channels: int) -> int:
        if self.head_dim is not None:
            assert channels % self.head_dim == 0, (channels, self.head_dim)
            return channels // self.head_dim
        return self.num_heads


SD14_UNET = UNetConfig()

# Tiny config for tests: same topology class (2 of 3 levels attentive, mid
# attention, skip concats, CFG) at ~1/4000 the parameters. Latent 16² keeps a
# 16²→8²→4² pyramid so store/blend resolutions exist.
TINY_UNET = UNetConfig(
    sample_size=16,
    in_channels=4,
    out_channels=4,
    block_channels=(32, 64, 64),
    attn_levels=(True, True, False),
    layers_per_block=1,
    num_heads=2,
    context_dim=32,
    context_len=16,
    groups=8,
    ff_mult=2,
)


def unet_attn_specs(cfg: UNetConfig):
    """Every attention call site in forward-call order, as
    ``(place, is_cross, resolution, heads, key_len, channels)`` tuples.

    Order contract (must match ``unet.apply_unet``'s call order): down blocks
    (per transformer block: self then cross), mid, up blocks. For SD14_UNET
    this yields exactly the reference's 32 hooked sites with the store slice
    ``down_cross[2:4] + up_cross[:3]`` landing on the 16×16 cross maps
    (`/root/reference/main.py:37-38`). ``channels`` (the site's feature-map
    width = its attention output width) sizes the phase-2 cross-attention
    cache buffers before tracing."""
    specs = []

    def site(place, level, depth):
        res = cfg.resolution_at(level)
        ch = cfg.block_channels[level]
        heads = cfg.heads_for(ch)
        for _ in range(depth):
            specs.append((place, False, res, heads, res * res, ch))       # self
            specs.append((place, True, res, heads, cfg.context_len, ch))  # cross

    for level in range(cfg.levels):                      # down
        for _ in range(cfg.layers_per_block):
            site("down", level, cfg.depth_at(level))
    site("mid", cfg.levels - 1, cfg.mid_depth)           # mid
    for level in reversed(range(cfg.levels)):            # up
        for _ in range(cfg.layers_per_block + 1):
            site("up", level, cfg.depth_at(level))
    return specs


def unet_layout(cfg: UNetConfig, store_cfg: Optional[StoreConfig] = None
                ) -> AttnLayout:
    if store_cfg is None:
        # The reference stores every ≤32²-pixel map (`/root/reference/main.py:131`);
        # scale that bound with the latent size so tiny test models store their
        # two lower pyramid levels the same way SD stores 32²/16²/8².
        store_cfg = StoreConfig(max_pixels=(cfg.sample_size // 2) ** 2)
    return build_layout(unet_attn_specs(cfg), store_cfg,
                        latent_size=cfg.sample_size)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Shape config for a transformer denoiser over patch tokens (diffusers'
    ``PixArtTransformer2DModel``): a patch embedding, ``num_layers`` blocks
    of self-attention, cross-attention to the caption and a feed-forward,
    each modulated by adaLN-single (one timestep MLP to six times the width,
    a learned ``(6, width)`` table a block), and a final modulated layer.
    The attribute names are the ones ``benchmarks/lib/pipeline.py`` reads."""

    kind: str = "transformer"
    sample_size: int = 128                 # latent side length
    patch_size: int = 2
    in_channels: int = 4
    # Twice the latent's channels where the variance is learned: the first
    # ``in_channels`` are ε, the rest are computed and dropped.
    out_channels: int = 8
    num_layers: int = 28
    num_heads: int = 16
    head_dim: int = 72
    context_dim: int = 1152                # the caption's projected width
    caption_channels: int = 4096           # the text tower's width
    context_len: int = 300                 # caption tokens, under a key mask
    norm_type: str = "ada_norm_single"
    activation: str = "gelu-approximate"
    ff_mult: int = 4
    attention_bias: bool = True
    use_additional_conditions: bool = False
    interpolation_scale: int = 2           # of the 2-D sin-cos positions
    freq_dim: int = 256                    # the timestep sinusoid's width
    kernel_dtype: str = "float32"          # as ``UNetConfig.kernel_dtype``

    @property
    def width(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def grid(self) -> int:
        """Tokens along a side: the latent's side over the patch."""
        return self.sample_size // self.patch_size


def transformer_attn_specs(cfg: TransformerConfig):
    """Every attention call site of ``transformer.apply_transformer`` in call
    order, as :func:`unet_attn_specs` gives them: per block its self site,
    then its cross site, all at the one token grid. Site ``2i`` is block
    ``i``'s self-attention and ``2i + 1`` its cross-attention; the place is
    ``"block"``, so the sites are named ``block0`` ... ``block<2n-1>``."""
    res, c = cfg.grid, cfg.width
    specs = []
    for _ in range(cfg.num_layers):
        specs.append(("block", False, res, cfg.num_heads, res * res, c))
        specs.append(("block", True, res, cfg.num_heads, cfg.context_len, c))
    return specs


def transformer_layout(cfg: TransformerConfig,
                       store_cfg: Optional[StoreConfig] = None) -> AttnLayout:
    """The layout of a transformer denoiser. Its one resolution is the token
    grid's: the default store keeps nothing (no map is small enough to stand
    for the pyramid's lower levels), and the layout's latent side is the
    grid's, so a controller's defaults are taken against that one level."""
    if store_cfg is None:
        store_cfg = StoreConfig(max_pixels=0)
    return build_layout(transformer_attn_specs(cfg), store_cfg,
                        latent_size=cfg.grid)


def denoiser_layout(cfg, store_cfg: Optional[StoreConfig] = None
                    ) -> AttnLayout:
    """The attention layout of a preset's denoiser (``PipelineConfig.unet``),
    whichever its kind."""
    if getattr(cfg, "kind", "unet") == "transformer":
        return transformer_layout(cfg, store_cfg)
    return unet_layout(cfg, store_cfg)


@dataclasses.dataclass(frozen=True)
class TextEncoderConfig:
    """CLIP-style causal text transformer (SD-1.4: ViT-L/14 text tower)."""

    vocab_size: int = 49408
    hidden_dim: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_length: int = 77
    ff_mult: int = 4
    activation: str = "quick_gelu"         # CLIP-L uses quick_gelu
    causal: bool = True
    # Attention projection width (heads·head_dim). CLIP is square (None →
    # hidden_dim); LDMBert projects 1280 → 8·64 = 512 and back.
    attn_inner_dim: Optional[int] = None
    # LDMBert's q/k/v projections carry no bias (out_proj does).
    attn_qkv_bias: bool = True
    # Checkpoint-name architecture: 'clip' (CLIPTextModel) | 'ldmbert'.
    arch: str = "clip"
    # Which layer's output the tower returns, as an index into the layers'
    # outputs (-1 the last, -2 the penultimate), and whether the final
    # LayerNorm is applied to it.
    output_layer: int = -1
    final_norm: bool = True
    # Width of the pooled output (``CLIPTextModelWithProjection``): the final
    # LayerNorm of the last layer's state at the first end-of-text position,
    # through a bias-free projection. None: the tower has no pooled output.
    projection_dim: Optional[int] = None
    kernel_dtype: str = "float32"          # as ``UNetConfig.kernel_dtype``
    # T5's encoder (``arch="t5"``): a feed-forward of ``intermediate_size``
    # in place of ``ff_mult`` times the width, and one relative-position
    # bias of this many buckets up to this distance. None for every other
    # tower, whose sizes these do not describe.
    intermediate_size: Optional[int] = None
    relative_attention_num_buckets: Optional[int] = None
    relative_attention_max_distance: Optional[int] = None

    @property
    def inner_dim(self) -> int:
        return self.attn_inner_dim or self.hidden_dim

SD14_TEXT = TextEncoderConfig()
TINY_TEXT = TextEncoderConfig(vocab_size=49408, hidden_dim=32, num_layers=2,
                              num_heads=2, max_length=16)


@dataclasses.dataclass(frozen=True)
class VAEConfig:
    """Latent autoencoder: KL (`AutoencoderKL`, SD) or VQ (`VQModel`, LDM).

    ``kind='vq'`` adds a codebook: decode first snaps each latent vector to
    its nearest codebook entry (the reference's `model.vqvae` decode path,
    `/root/reference/ptp_utils.py:124`)."""

    in_channels: int = 3
    latent_channels: int = 4
    base_channels: int = 128
    channel_mults: Tuple[int, ...] = (1, 2, 4, 4)
    layers_per_block: int = 2
    groups: int = 32
    scaling_factor: float = 0.18215        # `/root/reference/ptp_utils.py:80`
    kind: str = "kl"                       # 'kl' | 'vq'
    num_codebook: int = 16384              # VQ only: codebook entries
    kernel_dtype: str = "float32"          # as ``UNetConfig.kernel_dtype``

SD14_VAE = VAEConfig()
TINY_VAE = VAEConfig(base_channels=16, channel_mults=(1, 2, 2), layers_per_block=1,
                     groups=8)  # 2 downsamples: 64² image ⇄ 16² latent


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Scheduler constants, scoped per backend — the knobs the reference
    scatters between pipeline defaults and explicit construction
    (`/root/reference/main.py:29` keeps SD's pipeline PNDM;
    `/root/reference/null_text.py:16-20` builds DDIM with clip_sample=False,
    set_alpha_to_one=False)."""

    kind: str = "ddim"              # default sampler: 'ddim' | 'plms' | 'dpm'
    num_train_timesteps: int = 1000
    beta_start: float = 0.00085
    beta_end: float = 0.012
    beta_schedule: str = "scaled_linear"
    set_alpha_to_one: bool = False
    clip_sample: bool = False
    # The SD pipeline's PNDM config uses steps_offset=1 (every sampled
    # timestep shifted up by one); the null-text DDIM construction leaves it 0.
    plms_steps_offset: int = 1
    ddim_steps_offset: int = 0
    # 'epsilon' (SD-1.x / SD-2.1-base) or 'v_prediction' (SD-2.1 768-v).
    prediction_type: str = "epsilon"

    def steps_offset(self, kind: str) -> int:
        return self.plms_steps_offset if kind == "plms" else self.ddim_steps_offset


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """A full backend: text encoder + U-Net + VAE + scheduler defaults."""

    name: str
    # The denoiser, of either kind: a ``UNetConfig``, or a
    # ``TransformerConfig`` (``kind="transformer"``).
    unet: Union[UNetConfig, "TransformerConfig"]
    # One tower's configuration, or a sequence of them whose outputs are
    # concatenated into one context (``Pipeline.text_params`` is then a list
    # of trees in the same order).
    text: Union[TextEncoderConfig, Tuple[TextEncoderConfig, ...]]
    vae: VAEConfig
    image_size: int = 512
    guidance_scale: float = 7.5            # `/root/reference/main.py:20`
    num_steps: int = 50
    scheduler: SchedulerConfig = SchedulerConfig()

    @property
    def latent_size(self) -> int:
        return self.unet.sample_size

    @property
    def towers(self) -> Tuple[TextEncoderConfig, ...]:
        """The text towers in order, whichever form ``text`` has."""
        return self.text if isinstance(self.text, tuple) else (self.text,)


SD14 = PipelineConfig("sd-v1.4", SD14_UNET, SD14_TEXT, SD14_VAE, image_size=512)
TINY = PipelineConfig("tiny", TINY_UNET, TINY_TEXT, TINY_VAE, image_size=64,
                      num_steps=4)

# LDM text2im-large-256 (`/root/reference/ptp_utils.py:98-126`): BERT-style
# (non-causal, gelu) 1280-d text encoder tokenized by BERT wordpiece
# (vocab 30522), 32² latent pyramid (256² image, f8 VQ autoencoder), heads at
# fixed head_dim 64 (5/10/20 per level), VQ codebook decode. Structure follows
# the CompVis txt2img-f8-large UNet: model_channels 320, mults (1,2,4,4),
# 2 res blocks/level, attention at the 32²/16²/8² levels.
LDM_UNET = UNetConfig(
    sample_size=32,
    in_channels=4,
    out_channels=4,
    block_channels=(320, 640, 1280, 1280),
    attn_levels=(True, True, True, False),
    layers_per_block=2,
    head_dim=64,
    context_dim=1280,
    context_len=77,
)
LDM_TEXT = TextEncoderConfig(vocab_size=30522, hidden_dim=1280, num_layers=32,
                             num_heads=8, max_length=77, activation="gelu",
                             causal=False, attn_inner_dim=8 * 64,
                             attn_qkv_bias=False, arch="ldmbert")
# scaling_factor stays 0.18215: the reference decodes BOTH backends through
# the same `latent2image` with the 1/0.18215 scale
# (`/root/reference/ptp_utils.py:79-85`, VQ call at `:124`).
# channel_mults (1,2,2,4) = 3 downsamples = f8 (the LDM VQ-f8 autoencoder):
# 256² image ⇄ 32² latent, matching LDM_UNET.sample_size.
LDM_VAE = VAEConfig(base_channels=128, channel_mults=(1, 2, 2, 4),
                    latent_channels=4, kind="vq", num_codebook=16384)
LDM256 = PipelineConfig("ldm-text2im-256", LDM_UNET, LDM_TEXT, LDM_VAE,
                        image_size=256, guidance_scale=5.0, num_steps=50,
                        scheduler=SchedulerConfig(
                            beta_start=0.0015, beta_end=0.0195,
                            plms_steps_offset=0))

# SD-2.1 family — the model the reference marks "Not work"
# (`/root/reference/main.py:27`); here a config, not a code change: OpenCLIP
# ViT-H text tower realized as 23 transformer layers (diffusers' checkpoint
# conversion truncates layer 24 so the final-LN output IS the penultimate
# hidden state SD-2 conditions on), gelu activation, 1024-wide context;
# U-Net at fixed head_dim 64. The 768-v variant predicts v, not ε.
SD21_TEXT = TextEncoderConfig(hidden_dim=1024, num_layers=23, num_heads=16,
                              activation="gelu")
SD21_UNET = UNetConfig(context_dim=1024, head_dim=64)
SD21_BASE = PipelineConfig("sd-v2.1-base", SD21_UNET, SD21_TEXT, SD14_VAE,
                           image_size=512)
SD21 = PipelineConfig(
    "sd-v2.1", dataclasses.replace(SD21_UNET, sample_size=96), SD21_TEXT,
    SD14_VAE, image_size=768,
    scheduler=SchedulerConfig(prediction_type="v_prediction"))

# High-resolution SD variant: same weights shapes, 128² latent (1024²
# image). The 128²-pixel self-attention sites (16384² score matrix, ~2GB
# per head in f32) are exactly the case ring/sequence-parallel attention
# exists for — pass an SpConfig to apply_unet to shard them over a mesh.
SD14_HR = PipelineConfig(
    "sd-v1.4-1024", dataclasses.replace(SD14_UNET, sample_size=128),
    SD14_TEXT, SD14_VAE, image_size=1024)

# Tiny LDM-shaped backend for tests: same architectural family as LDM256
# (per-level heads via head_dim, non-causal no-qkv-bias text encoder, VQ
# decoder, LDM β schedule) at toy sizes.
TINY_LDM_UNET = dataclasses.replace(
    TINY_UNET, num_heads=1, head_dim=16, block_channels=(32, 64, 64))
TINY_LDM_TEXT = dataclasses.replace(
    TINY_TEXT, causal=False, activation="gelu", attn_inner_dim=32,
    attn_qkv_bias=False, arch="ldmbert", vocab_size=30522)
TINY_LDM_VAE = dataclasses.replace(TINY_VAE, kind="vq", num_codebook=64)
TINY_LDM = PipelineConfig("tiny-ldm", TINY_LDM_UNET, TINY_LDM_TEXT,
                          TINY_LDM_VAE, image_size=64, num_steps=4,
                          guidance_scale=5.0,
                          scheduler=SchedulerConfig(
                              beta_start=0.0015, beta_end=0.0195,
                              plms_steps_offset=0))

# Tiny SD-2.1-shaped backend for tests: what `sd21` forces at toy sizes —
# v-prediction, heads by a fixed head_dim, a gelu text tower, and a latent
# whose levels are not powers of two (12 / 6 / 3: no 16² level, so the
# controllers' defaults come from the layout, 48² image).
TINY_V = PipelineConfig(
    "tiny-v",
    dataclasses.replace(TINY_UNET, sample_size=12, num_heads=1, head_dim=16),
    dataclasses.replace(TINY_TEXT, activation="gelu"), TINY_VAE,
    image_size=48, num_steps=4,
    scheduler=SchedulerConfig(prediction_type="v_prediction"))

# SDXL-base-1.0 (arXiv 2307.01952; the published unet / text_encoder /
# text_encoder_2 / vae config.json): three levels with no attention at the
# top and transformers 2 and 10 blocks deep below it (the mid block 10), heads
# of 64, two text towers whose penultimate states (no final LayerNorm) are
# concatenated into a 2048-wide context, and the second tower's pooled text
# with six embedded sizes added to the time embedding. Kernels are stored in
# bfloat16: 3.47 B parameters are 13.9 GB in float32, which leaves a 16 GB
# chip nothing beside them.
SDXL_UNET = UNetConfig(
    sample_size=128, block_channels=(320, 640, 1280),
    attn_levels=(False, True, True), transformer_depth=(0, 2, 10),
    head_dim=64, context_dim=2048, addition_embed_in=2816,
    addition_sizes=(1024, 1024, 0, 0, 1024, 1024), kernel_dtype="bfloat16")
SDXL_TEXT = (
    TextEncoderConfig(output_layer=-2, final_norm=False,
                      kernel_dtype="bfloat16"),
    TextEncoderConfig(hidden_dim=1280, num_layers=32, num_heads=20,
                      activation="gelu", output_layer=-2, final_norm=False,
                      projection_dim=1280, kernel_dtype="bfloat16"))
SDXL = PipelineConfig(
    "sdxl-base-1.0", SDXL_UNET, SDXL_TEXT,
    dataclasses.replace(SD14_VAE, scaling_factor=0.13025,
                        kernel_dtype="bfloat16"),
    image_size=1024, guidance_scale=5.0)

# Tiny SDXL-shaped backend for tests: what `sdxl` forces at toy sizes — no
# attention at the top level, depth by level, two towers of different widths
# into one context, the pooled text and the sizes beside the time step,
# bfloat16 kernels, and a latent (24 / 12 / 6) whose attentive levels start below its own side and hold no 16 (the edit's
# default side is a quarter of the latent's, 6).
TINY_XL_UNET = dataclasses.replace(
    TINY_UNET, sample_size=24, attn_levels=(False, True, True),
    transformer_depth=(0, 1, 2), num_heads=1, head_dim=16, context_dim=80,
    addition_embed_in=48 + 6 * 8, addition_time_dim=8,
    addition_sizes=(96, 96, 0, 0, 96, 96), kernel_dtype="bfloat16")
TINY_XL_TEXT = (
    dataclasses.replace(TINY_TEXT, output_layer=-2, final_norm=False,
                        kernel_dtype="bfloat16"),
    dataclasses.replace(TINY_TEXT, hidden_dim=48, num_layers=3, num_heads=3,
                        activation="gelu", output_layer=-2, final_norm=False,
                        projection_dim=48, kernel_dtype="bfloat16"))
TINY_XL = PipelineConfig(
    "tiny-xl", TINY_XL_UNET, TINY_XL_TEXT,
    dataclasses.replace(TINY_VAE, scaling_factor=0.13025,
                        kernel_dtype="bfloat16"),
    image_size=96, num_steps=4, guidance_scale=5.0)

# PixArt-Sigma at 1024² (arXiv 2403.04692; `PixArt-Sigma-XL-2-1024-MS`'s
# transformer config.json and `pixart_sigma_sdxlvae_T5_diffusers`'s
# text_encoder, vae and scheduler): a 28-block DiT over 2×2 patches of the
# 128² latent (4,096 tokens of 1152, 16 heads of 72) with adaLN-single and a
# learned variance, conditioned on T5-v1.1-XXL's encoder over 300 tokens
# under a key mask, decoded by SDXL's autoencoder. Guidance 4.5 is the
# published pipeline's default; DDIM over the checkpoint's linear betas.
# Kernels are stored in bfloat16, as SDXL's: 5.4 B parameters.
PIXART_SIGMA_TEXT = TextEncoderConfig(
    vocab_size=32128, hidden_dim=4096, num_layers=24, num_heads=64,
    max_length=300, activation="gated-gelu", causal=False,
    attn_qkv_bias=False, arch="t5", kernel_dtype="bfloat16",
    intermediate_size=10240, relative_attention_num_buckets=32,
    relative_attention_max_distance=128)
PIXART_SCHEDULER = SchedulerConfig(beta_start=0.0001, beta_end=0.02,
                                   beta_schedule="linear")
PIXART_SIGMA = PipelineConfig(
    "pixart-sigma-1024", TransformerConfig(kernel_dtype="bfloat16"),
    PIXART_SIGMA_TEXT, SDXL.vae, image_size=1024, guidance_scale=4.5,
    scheduler=PIXART_SCHEDULER)

# Tiny PixArt-shaped backend for tests: what `pixart_sigma` forces at toy
# sizes — a transformer denoiser over 2×2 patches with adaLN-single and a
# learned variance, a T5 tower (RMS norms, relative buckets, gated GELU)
# under a caption key mask, bfloat16 kernels.
TINY_DIT = PipelineConfig(
    "tiny-dit",
    TransformerConfig(sample_size=16, num_layers=2, num_heads=2, head_dim=8,
                      context_dim=16, caption_channels=32, context_len=16,
                      kernel_dtype="bfloat16"),
    dataclasses.replace(PIXART_SIGMA_TEXT, hidden_dim=32, num_layers=2,
                        num_heads=2, max_length=16, intermediate_size=48),
    dataclasses.replace(TINY_VAE, scaling_factor=0.13025,
                        kernel_dtype="bfloat16"),
    image_size=64, num_steps=4, guidance_scale=4.5,
    scheduler=PIXART_SCHEDULER)


# The one preset-name → PipelineConfig resolution map (CLI commands,
# `p2p-tpu check`, tools/parity_real_weights.py all resolve through it).
# The CLI's argparse `choices` tuples are deliberate literal copies — the
# parser must stay jax-free for instant --help — pinned against this dict
# by tests/test_cli.py::test_every_cli_preset_resolves_to_a_config; adding
# a preset means this dict plus those two tuples (the test fails loudly
# until all agree).
PRESET_CONFIGS = {
    "tiny": TINY,
    "sd14": SD14,
    "sd21": SD21,
    "sd21base": SD21_BASE,
    "ldm256": LDM256,
    "tiny_ldm": TINY_LDM,
    "tiny_v": TINY_V,
    "sdxl": SDXL,
    "tiny_xl": TINY_XL,
    "pixart_sigma": PIXART_SIGMA,
    "tiny_dit": TINY_DIT,
}
