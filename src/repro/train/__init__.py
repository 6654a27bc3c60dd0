"""Training utilities.

``repro.train.checkpoint`` (decision-forest training checkpoints, DESIGN.md
§11) is numpy-only and imported from inside ``Learner.train``. Lazy re-export
keeps the light path light: importing ``repro.train.checkpoint`` must not pay
for jax.
"""
_CKPT_SYMBOLS = ("CheckpointPolicy", "CheckpointSession", "as_policy",
                 "latest_checkpoint", "open_session", "resume_training",
                 "write_checkpoint")


def __getattr__(name):
    if name in _CKPT_SYMBOLS:
        from repro.train import checkpoint
        return getattr(checkpoint, name)
    raise AttributeError(f"module 'repro.train' has no attribute {name!r}")


__all__ = list(_CKPT_SYMBOLS)
