"""Elastic data + step helpers.

Names resolve lazily: the AGENT imports ``trainer.elastic.reshape`` for
its worker channels, and an eager import of this package would pull jax
(``ElasticTrainer``, ``DevicePrefetcher``) into the one process that
must stay off it — a process that initialises a JAX backend owns the
chip its workers need.
"""

import importlib

_EXPORTS = {
    "ElasticSampler": "sampler",
    "ElasticDataLoader": "dataloader",
    "ElasticDataset": "dataset",
    "ElasticTrainer": "trainer",
    "DevicePrefetcher": "prefetch",
    "ShmBatchWriter": "shm_loader",
    "ShmDataLoader": "shm_loader",
}

__all__ = list(_EXPORTS)


def __getattr__(name):
    try:
        module = _EXPORTS[name]
    except KeyError:
        raise AttributeError(name) from None
    return getattr(
        importlib.import_module(f"{__name__}.{module}"), name
    )
