"""Image classifier (``ImageClassifierTask``): procedural
class-conditional images, one output query per image."""

import numpy as np

from benchmarks.reference import perceiver_io as ref
from benchmarks.tasks import program_kwargs

loss_sum = ref.image_loss_sum


def program_task(cfg: dict):
    from perceiver_tpu.tasks import ImageClassifierTask as cls

    kwargs = program_kwargs(cls, cfg)
    kwargs["image_shape"] = tuple(kwargs["image_shape"])
    return cls, kwargs


def make_batch(rng, rows: int, cfg: dict, blobs: int = 4) -> dict:
    """Class-conditional Gaussian blobs plus pixel noise, normalised to
    about [-1, 1]: a class fixes the blobs' layout, a row jitters it."""
    h, w, c = cfg["image_shape"]
    labels = rng.integers(0, cfg["num_classes"], rows, dtype=np.int32)
    cy = np.empty((rows, blobs))
    cx, sy, sx = np.empty_like(cy), np.empty_like(cy), np.empty_like(cy)
    amp = np.empty((rows, blobs, c))
    for i, cls in enumerate(labels):
        g = np.random.default_rng([int(cls), 13])
        cy[i], cx[i] = g.uniform(0.2, 0.8, blobs), g.uniform(0.2, 0.8, blobs)
        sy[i], sx[i] = (g.uniform(0.08, 0.25, blobs),
                        g.uniform(0.08, 0.25, blobs))
        amp[i] = g.uniform(0.3, 1.0, (blobs, c))
    cy += rng.uniform(-0.05, 0.05, (rows, 1))
    cx += rng.uniform(-0.05, 0.05, (rows, 1))
    ey = np.exp(-((np.linspace(0, 1, h)[None, None] - cy[..., None])
                  / sy[..., None]) ** 2)
    ex = np.exp(-((np.linspace(0, 1, w)[None, None] - cx[..., None])
                  / sx[..., None]) ** 2)
    img = np.einsum("bkh,bkw,bkc->bhwc", ey, ex, amp,
                    optimize=True).astype(np.float32) / (0.5 * blobs)
    img += 0.05 * rng.standard_normal(img.shape, dtype=np.float32)
    return {"image": (img - 0.5) / 0.5, "label": labels,
            "valid": np.ones(rows, bool)}


def tokens_per_row(cfg: dict) -> int:
    h, w, _ = cfg["image_shape"]
    return int(h * w)


def flop_shape(cfg: dict) -> dict:
    """Pixels with their Fourier position features; one query over the
    classes; the pixels need no gradient."""
    *spatial, channels = cfg["image_shape"]
    return {"positions": int(np.prod(spatial)),
            "channels": int(channels + len(spatial)
                            * (2 * cfg["num_frequency_bands"] + 1)),
            "queries": 1.0,
            "classes": int(cfg["num_classes"]),
            "input_grad": False}


def reference_batches(pool, cfg: dict, trainer_seed: int, steps: int):
    import jax.numpy as jnp

    del cfg, trainer_seed  # nothing is drawn inside the step
    return [{"image": jnp.asarray(b["image"]),
             "label": jnp.asarray(b["label"])} for b in pool[:steps]]
