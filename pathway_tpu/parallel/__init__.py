"""pw.parallel — device-mesh scale-out primitives.

Reference parity: the reference scales out with timely's communication crate
(hash-partitioned exchange over shared-memory channels / TCP,
external/timely-dataflow/communication/, SURVEY.md §2.2). The TPU-native
equivalent keeps a host control plane but moves the numeric data plane onto
the chip interconnect: records are bucketized by key hash in XLA and shuffled
with `all_to_all` over the mesh (ICI intra-pod, DCN across pods).

The package namespace is lazy (PEP 562): importing `pathway_tpu.parallel`
must NOT pull in jax, because every Session imports `process_mesh` (a
pure-socket module) and mesh-less pipelines would otherwise pay the whole
jax-ecosystem import on their first wave.
"""

_EXPORTS = {
    "default_mesh": "mesh",
    "make_mesh": "mesh",
    "replicate": "mesh",
    "shard_rows": "mesh",
    "ExchangeResult": "exchange",
    "exchange_by_key": "exchange",
    "partition_counts": "exchange",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    target = _EXPORTS.get(name)
    if target is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    mod = importlib.import_module(f"pathway_tpu.parallel.{target}")
    val = getattr(mod, name)
    globals()[name] = val  # cache: subsequent access skips __getattr__
    return val


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
