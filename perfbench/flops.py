"""Operations a training step requires, from its shapes.

The forward's count is the architecture's own: ``forward_flops`` of the
plain reference that the configuration names, counted for the real atoms
and edges only (pad excluded), as matrix multiplications at 2 operations
per multiply-add. The backward pass is counted as twice the forward, so a
training step is 3x the forward.
"""
from __future__ import annotations

from perfbench import harness


def train_step_flops(cfg: dict, **counts) -> int:
    """Forward + backward operations of one training step; ``counts``:
    ``structures``, ``atoms`` and ``edges``, real ones only."""
    return 3 * harness.reference(cfg).forward_flops(cfg, **counts)
