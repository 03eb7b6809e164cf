"""Heatmap pose estimation network for the openpose preprocessor.

Reference behavior replaced: swarm/pre_processors/controlnet.py:46-47
(`OpenposeDetector.from_pretrained("lllyasviel/ControlNet")` — a torch
body-pose network run per job). TPU redesign: a compact fully-conv
heatmap network in flax (strided conv encoder -> residual trunk -> 18
COCO-keypoint heatmaps at 1/8 resolution), resident and jitted once per
canvas bucket; keypoints read out as per-channel argmax + confidence.
Weights follow weights.py policy: tiny/test names random-init, real names
fail loudly until pose-weight conversion lands.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax.numpy as jnp

from .layers import Conv

# COCO-18 keypoint scheme (the openpose body model's output order)
N_KEYPOINTS = 18
# limb connectivity for skeleton rendering (keypoint index pairs)
LIMBS = (
    (0, 1), (1, 2), (2, 3), (3, 4), (1, 5), (5, 6), (6, 7), (1, 8),
    (8, 9), (9, 10), (1, 11), (11, 12), (12, 13), (0, 14), (14, 16),
    (0, 15), (15, 17),
)


@dataclasses.dataclass(frozen=True)
class PoseConfig:
    image_size: int = 368  # openpose canonical input canvas
    widths: tuple[int, ...] = (64, 128, 256)
    trunk_blocks: int = 4
    n_keypoints: int = N_KEYPOINTS


TINY_POSE = PoseConfig(image_size=64, widths=(8, 16), trunk_blocks=1)


class _ResBlock(nn.Module):
    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        h = nn.relu(Conv(self.width, (3, 3), dtype=self.dtype)(x))
        h = Conv(self.width, (3, 3), dtype=self.dtype)(h)
        return nn.relu(x + h)


class PoseNet(nn.Module):
    config: PoseConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, pixels):
        """[B, S, S, 3] in [-1, 1] -> heatmaps [B, S/2^len(widths), ..,
        n_keypoints] (sigmoid confidence per cell)."""
        x = pixels
        for w in self.config.widths:
            x = nn.relu(
                Conv(w, (3, 3), strides=(2, 2), dtype=self.dtype)(x)
            )
        for _ in range(self.config.trunk_blocks):
            x = _ResBlock(self.config.widths[-1], dtype=self.dtype)(x)
        heat = Conv(
            self.config.n_keypoints, (1, 1), dtype=self.dtype, name="heatmaps"
        )(x)
        return nn.sigmoid(heat)


# --- real CMU body-pose network (lllyasviel/ControlNet body_pose_model) ---

# COCO limb pairs and their PAF channel pairs, the standard openpose
# grouping tables (1-based keypoint ids in the original; stored 0-based)
LIMB_SEQ = (
    (1, 2), (1, 5), (2, 3), (3, 4), (5, 6), (6, 7), (1, 8), (8, 9),
    (9, 10), (1, 11), (11, 12), (12, 13), (1, 0), (0, 14), (14, 16),
    (0, 15), (15, 17), (2, 16), (5, 17),
)
PAF_IDX = (
    (12, 13), (20, 21), (14, 15), (16, 17), (22, 23), (24, 25), (0, 1),
    (2, 3), (4, 5), (6, 7), (8, 9), (10, 11), (28, 29), (30, 31),
    (34, 35), (32, 33), (36, 37), (18, 19), (26, 27),
)


class OpenposeBody(nn.Module):
    """CMU 6-stage CPM body network (VGG-19 feature trunk + per-stage
    PAF/heatmap branches), flax/NHWC, module names mirroring the
    pytorch-openpose state dict (`model0.conv1_1`,
    `model1_1.conv5_1_CPM_L1`, `model2_1.Mconv1_stage2_L1`, ...) so
    conversion.convert_openpose_body is mechanical.

    Replaces the compact stand-in PoseNet for real
    `lllyasviel/ControlNet` annotator weights (reference
    swarm/pre_processors/controlnet.py:46-47). Returns (paf [B,H/8,W/8,38],
    heatmap [B,H/8,W/8,19])."""

    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, pixels):
        import functools

        relu = nn.relu
        pool = functools.partial(
            nn.max_pool, window_shape=(2, 2), strides=(2, 2)
        )

        class _Scope(nn.Module):
            """Named sub-scope so params nest as model0/conv1_1/..."""

            layers: tuple
            dtype: jnp.dtype

            @nn.compact
            def __call__(self, x):
                outer = self.layers
                for kind, args in outer:
                    if kind == "conv":
                        name, ch, k = args
                        x = Conv(
                            ch, (k, k),
                            padding=((k // 2, k // 2), (k // 2, k // 2)),
                            dtype=self.dtype, name=name,
                        )(x)
                    elif kind == "relu":
                        x = relu(x)
                    else:  # pool
                        x = pool(x)
                return x

        vgg = []
        for name, ch in (
            ("conv1_1", 64), ("conv1_2", 64),
        ):
            vgg += [("conv", (name, ch, 3)), ("relu", None)]
        vgg += [("pool", None)]
        for name, ch in (("conv2_1", 128), ("conv2_2", 128)):
            vgg += [("conv", (name, ch, 3)), ("relu", None)]
        vgg += [("pool", None)]
        for name, ch in (
            ("conv3_1", 256), ("conv3_2", 256), ("conv3_3", 256),
            ("conv3_4", 256),
        ):
            vgg += [("conv", (name, ch, 3)), ("relu", None)]
        vgg += [("pool", None)]
        for name, ch in (
            ("conv4_1", 512), ("conv4_2", 512), ("conv4_3_CPM", 256),
            ("conv4_4_CPM", 128),
        ):
            vgg += [("conv", (name, ch, 3)), ("relu", None)]
        feats = _Scope(tuple(vgg), self.dtype, name="model0")(pixels)

        def stage1(branch, out_ch):
            layers = []
            for i in (1, 2, 3):
                layers += [
                    ("conv", (f"conv5_{i}_CPM_L{branch}", 128, 3)),
                    ("relu", None),
                ]
            layers += [
                ("conv", (f"conv5_4_CPM_L{branch}", 512, 1)), ("relu", None),
                ("conv", (f"conv5_5_CPM_L{branch}", out_ch, 1)),
            ]
            return tuple(layers)

        def stage_t(t, branch, out_ch):
            layers = []
            for i in (1, 2, 3, 4, 5):
                layers += [
                    ("conv", (f"Mconv{i}_stage{t}_L{branch}", 128, 7)),
                    ("relu", None),
                ]
            layers += [
                ("conv", (f"Mconv6_stage{t}_L{branch}", 128, 1)),
                ("relu", None),
                ("conv", (f"Mconv7_stage{t}_L{branch}", out_ch, 1)),
            ]
            return tuple(layers)

        paf = _Scope(stage1(1, 38), self.dtype, name="model1_1")(feats)
        heat = _Scope(stage1(2, 19), self.dtype, name="model1_2")(feats)
        for t in range(2, 7):
            x = jnp.concatenate([paf, heat, feats], axis=-1)
            paf = _Scope(stage_t(t, 1, 38), self.dtype, name=f"model{t}_1")(x)
            heat = _Scope(stage_t(t, 2, 19), self.dtype, name=f"model{t}_2")(x)
        return paf, heat
