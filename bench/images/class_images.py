"""CIFAR-shaped class-textured images, float32 (N, img, img, channels) in
[0, 1].

A copy of the program's `data.synthetic.class_images`, kept here so that
no change to the program moves the traffic.
"""
import numpy as np


def make(seed: int, n: int, img: int = 32, channels: int = 3,
         n_classes: int = 10) -> np.ndarray:
    """Class-conditional textured images: a sine grating whose frequency
    is set by the class, tinted per channel, with Gaussian noise."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0, 0]))
    labels = rng.integers(0, n_classes, n)
    yy, xx = np.mgrid[0:img, 0:img].astype(np.float32) / img
    imgs = np.empty((n, img, img, channels), np.float32)
    for i, c in enumerate(labels):
        fx, fy = 1 + c % 5, 1 + c // 5
        base = 0.5 + 0.35 * np.sin(2 * np.pi * (fx * xx + fy * yy))
        noise = rng.normal(0, 0.1, (img, img, channels))
        phase = 2 * np.pi * np.arange(channels) / channels + c
        imgs[i] = np.clip(
            base[..., None] * (0.8 + 0.2 * np.cos(phase)) + noise, 0, 1)
    return imgs
