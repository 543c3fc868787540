"""Runtime: multi-host init, mesh construction, supervision.

Lazy re-exports (PEP 562): the stdlib-only parents (``tpuic.supervise``,
``tpuic.runtime.gang``) import this package without pulling in jax.
"""

_LAZY = {
    "initialize": ("tpuic.runtime.distributed", "initialize"),
    "runtime_info": ("tpuic.runtime.distributed", "runtime_info"),
    "make_mesh": ("tpuic.runtime.mesh", "make_mesh"),
    "data_sharding": ("tpuic.runtime.mesh", "data_sharding"),
    "replicated_sharding": ("tpuic.runtime.mesh", "replicated_sharding"),
}


def __getattr__(name):
    if name in _LAZY:
        import importlib
        module, attr = _LAZY[name]
        return getattr(importlib.import_module(module), attr)
    raise AttributeError(f"module 'tpuic.runtime' has no attribute '{name}'")


def __dir__():
    return sorted(list(globals()) + list(_LAZY))
