"""Python side of the embeddable C serving ABI.

The reference exposes ~300 ``flexflow_*`` C functions
(reference ``src/c/flexflow_c.cc:1-2680``) so non-Python hosts can
drive it through opaque handles. The TPU framework's C surface is
deliberately narrow — serving is the embed case that matters — and maps
1:1 onto :class:`RequestManager`'s step-wise API:

    ff_serve_init(config_json)        -> init
    ff_serve_register_request(...)    -> register_request
    ff_serve_step()                   -> step
    ff_serve_num_active()             -> num_active
    ff_serve_fetch(rid, buf, cap)     -> fetch
    ff_serve_shutdown()               -> shutdown

State is one module-global engine + manager, mirroring the reference's
singleton (``request_manager.cc`` ``get_request_manager``). The C shim
(:mod:`flexflow_tpu.native` ``serve_c_api.cpp``) embeds CPython and
forwards into this module, so a plain C host only links
``libffserve.so`` + ``libpython``.

Config JSON accepted by :func:`init`::

    {
      "family": "llama",            # model family in flexflow_tpu.models
      "model": {...},               # family Config kwargs (e.g. hidden_size)
      "serving": {...},             # ServingConfig kwargs
      "max_new_tokens": 32,         # default per-request budget
      "seed": 0,                    # random-weight init seed
      "platform": "cpu"             # optional: force a JAX platform
    }
"""
from __future__ import annotations

import dataclasses
import json
from typing import List, Optional

_STATE: dict = {}


def init(cfg_json: str) -> int:
    """Build the engine + request manager. Returns 0 on success."""
    cfg = json.loads(cfg_json) if cfg_json else {}
    platform = cfg.get("platform")
    import jax

    if platform:
        jax.config.update("jax_platforms", platform)
    import importlib

    import jax.numpy as jnp

    def _dtypes(d, *keys):
        # JSON carries dtypes as strings ("bfloat16", "float32")
        return {
            k: getattr(jnp, v) if k in keys and isinstance(v, str) else v
            for k, v in d.items()
        }

    family = cfg.get("family", "llama")
    mod = importlib.import_module(f"flexflow_tpu.models.{family}")
    model_kw = _dtypes(cfg.get("model", {}), "dtype")
    # every family module exposes a config() factory over DecoderConfig
    mcfg = mod.config(**model_kw)
    from .engine import InferenceEngine, ServingConfig
    from .request_manager import RequestManager

    sc = ServingConfig(**_dtypes(cfg.get("serving", {}), "cache_dtype"))
    params = mod.init_params(jax.random.PRNGKey(cfg.get("seed", 0)), mcfg)
    if sc.replicas > 1 or sc.prefill_replicas:
        # Cluster serving: the C host drives the ClusterManager through
        # the SAME step loop — register/step/num_active/fetch all read
        # the RequestStatus-shaped cluster requests, so a request SHED
        # by SLO admission is terminal (ERROR) exactly like the PR-2
        # unservable-request path: num_active drops, fetch returns
        # None, and the host's loop never spins on it.
        from .cluster import ClusterManager

        rm = ClusterManager.build(
            mod, mcfg, params, sc, seed=cfg.get("seed", 0)
        )
    else:
        rm = RequestManager(InferenceEngine(mod, mcfg, params, sc))
    _STATE["rm"] = rm
    _STATE["max_new_tokens"] = int(cfg.get("max_new_tokens", 32))
    return 0


def register_request(tokens: List[int], max_new: int = 0) -> int:
    """Queue a prompt; returns the request id (guid)."""
    from .batch_config import GenerationConfig

    rm = _STATE["rm"]
    gen = GenerationConfig(
        max_new_tokens=max_new or _STATE["max_new_tokens"]
    )
    return rm.register_request([int(t) for t in tokens], gen)


def step() -> int:
    """One scheduling step. Returns 1 while work remains, else 0."""
    return 1 if _STATE["rm"].step() else 0


def num_active() -> int:
    """Requests not yet terminal (pending + in slots). ERROR requests
    count as done — a request that can never be served must not keep
    the C host's step loop spinning."""
    from .request_manager import TERMINAL_STATUSES

    rm = _STATE["rm"]
    return sum(
        1 for r in rm.requests.values()
        if r.status not in TERMINAL_STATUSES
    )


def fetch(rid: int) -> Optional[List[int]]:
    """Output tokens of a COMPLETED request, else None."""
    from .request_manager import RequestStatus

    rm = _STATE["rm"]
    req = rm.requests.get(rid)
    if req is None or req.status is not RequestStatus.COMPLETED:
        return None
    return list(req.output_tokens)


def shutdown() -> int:
    _STATE.clear()
    return 0
