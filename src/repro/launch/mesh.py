"""Production mesh + sharding rules.

Mesh: (data=16, model=16) per pod; (pod=2, data=16, model=16) across pods.
Importing this module never touches jax device state — mesh construction is
behind functions.

Sharding rules are path-based (MaxText-style logical axes):
  * parameters: largest non-'model' axis FSDP-shards over ('pod','data');
    head/expert/ff/vocab axes shard over 'model' when divisible;
  * batch shards over ('pod','data');
  * KV caches: kv-heads over 'model' when divisible, otherwise the cache
    *sequence* axis shards over 'model' (MQA case); batch over ('pod','data')
    unless batch == 1 (long_500k), where sequence sharding carries all of it.
"""
from __future__ import annotations

import math
import re
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) == need:
        return jax.make_mesh(shape, axes)
    if len(devs) < need:
        raise RuntimeError(
            f"need {need} devices for {shape}, have {len(devs)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512 (dryrun.py "
            f"sets this before importing jax)")
    return Mesh(np.asarray(devs[:need]).reshape(shape), axes)


def make_test_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    need = math.prod(shape)
    devs = jax.devices()
    assert len(devs) >= need
    return Mesh(np.asarray(devs[:need]).reshape(shape), axes)


class ShardingRules:
    """Maps parameter/batch/cache paths to PartitionSpecs for a given mesh."""

    def __init__(self, mesh: Mesh, *, fsdp: bool = True,
                 shard_cache_seq_for_mqa: bool = True):
        self.mesh = mesh
        self.axes = mesh.axis_names
        self.model_size = mesh.shape["model"]
        dp = [a for a in ("pod", "data") if a in self.axes]
        self.dp: Any = tuple(dp) if len(dp) > 1 else dp[0]
        self.fsdp_axis: Any = self.dp if fsdp else None
        self.shard_cache_seq_for_mqa = shard_cache_seq_for_mqa

    # -- helpers ----------------------------------------------------------
    # pjit argument shardings require EXACT divisibility (uneven shards are
    # rejected) — every rule checks strictly and falls back to an alternate
    # axis or replication.

    @property
    def dp_size(self) -> int:
        ax = self.fsdp_axis if isinstance(self.fsdp_axis, tuple) else \
            (self.fsdp_axis,)
        return math.prod(self.mesh.shape[a] for a in ax if a)

    def _model_if_div(self, dim: int) -> Optional[str]:
        return "model" if dim > 0 and dim % self.model_size == 0 else None

    def _fsdp_if_div(self, dim: int):
        if self.fsdp_axis is None:
            return None
        return self.fsdp_axis if dim % self.dp_size == 0 else None

    # -- parameters -------------------------------------------------------

    def param_spec(self, path: str, shape: Tuple[int, ...]) -> P:
        stacked = bool(re.search(r"segments/\d+/", path))
        base = self._param_base(path, shape[1:] if stacked else shape)
        if stacked:
            base = (None,) + base
        assert len(base) == len(shape), (path, shape, base)
        return P(*base)

    def _param_base(self, path: str, s: Tuple[int, ...]) -> Tuple:
        fs = self._fsdp_if_div
        md = self._model_if_div
        # vocab is padded to a mesh multiple (ModelConfig.padded_vocab).
        # NEVER shard d_model of embed/head: the tied-head matmul would
        # contract over a sharded axis and all-reduce [B,T,V] activations
        # (§Perf hillclimb 1 — was ~190 GB/device/step on gemma train_4k).
        if path.endswith("embed/table"):
            return (md(s[0]), None)
        if path.endswith("head/w"):
            return (None, md(s[1]))
        # attention (3-D [d, heads, hd] — rwkv reuses wk/wv names for 2-D).
        # NEVER shard head_dim: a sharded score/AV contraction forces
        # per-chunk all-reduces and carry resharding in the streaming scan
        # (§Perf hillclimb: 32 GiB/chunk-iter on gemma).  Heads that do not
        # divide the model axis replicate (attention params are small; the
        # model axis still carries the MLP).
        for nm in ("wq/w", "wk/w", "wv/w"):
            if path.endswith(nm) and len(s) == 3:
                return (fs(s[0]), md(s[1]), None)
        for nm in ("wq/b", "wk/b", "wv/b"):
            if path.endswith(nm) and len(s) == 2:
                return (md(s[0]), None)
        if path.endswith("wo/w") and len(s) == 2 and ("attn" in path or
                                                      "xattn" in path):
            return (md(s[0]), fs(s[1]))
        # MoE
        if path.endswith("router/w"):
            return (fs(s[0]), md(s[1]))
        if "w_up" in path or "w_gate" in path:
            return (md(s[0]), fs(s[1]), None)
        if "w_down" in path:
            return (md(s[0]), None, fs(s[2]))
        if "gate_x" in path or "gate_a" in path:   # rglru block-diag gates
            return (md(s[0]), None, None)
        # MLP / rwkv / rglru dense params [d_in, d_out]
        if len(s) == 2 and path.endswith("/w"):
            # shard the bigger of ff-style dims over model
            if s[1] >= s[0]:
                if md(s[1]):
                    return (fs(s[0]), md(s[1]))
                return (md(s[0]), fs(s[1]))
            if md(s[0]):
                return (md(s[0]), fs(s[1]))
            return (fs(s[0]), md(s[1]))
        if len(s) == 2 and ("lora" in path or path.endswith("mu")):
            return (None, None)
        if len(s) == 3:      # e.g. rwkv lora_a [d,5,r] / lora_b [5,r,d]
            return (None, None, None) if s[0] <= 8 else (fs(s[0]), None, None)
        if len(s) == 1:
            return (None,)
        return tuple(None for _ in s)

    def _combined_if_div(self, dim: int):
        """('pod','data','model') stacked on one axis when divisible."""
        ax = (self.fsdp_axis if isinstance(self.fsdp_axis, tuple)
              else (self.fsdp_axis,)) if self.fsdp_axis else ()
        combo = tuple(a for a in ax if a) + ("model",)
        size = self.dp_size * self.model_size
        if dim % size == 0:
            return combo
        return self._fsdp_if_div(dim) or self._model_if_div(dim)

    def params_shardings(self, params_shapes) -> Any:
        """pytree of NamedSharding matching a pytree of ShapeDtypeStruct."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(params_shapes)
        out = []
        for path, leaf in flat:
            key = "/".join(_p(p) for p in path)
            spec = self.param_spec(key, tuple(leaf.shape))
            out.append(NamedSharding(self.mesh, spec))
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(params_shapes), out)

    # -- batch / activations ----------------------------------------------

    def batch_spec(self, shape: Tuple[int, ...], batch_size: int) -> P:
        dp = self.dp if batch_size > 1 else None
        return P(dp, *(None,) * (len(shape) - 1))

    def batch_shardings(self, batch_specs) -> Any:
        def one(leaf):
            return NamedSharding(self.mesh,
                                 self.batch_spec(leaf.shape, leaf.shape[0]))
        return jax.tree.map(one, batch_specs)

    # -- decode state -----------------------------------------------------

    def cache_spec(self, path: str, shape: Tuple[int, ...], batch: int,
                   n_kv: int) -> P:
        """Shapes carry a leading [count] (stacked units) axis."""
        dp = self.dp if batch > 1 else None
        kv_sharded = n_kv % self.model_size == 0
        if path.endswith("/k") or path.endswith("/v") or \
                path.endswith("xk") or path.endswith("xv"):
            # [count, B, L, Kh, hd]
            if kv_sharded:
                return P(None, dp, None, "model", None)
            if self.shard_cache_seq_for_mqa:
                return P(None, dp, "model", None, None)
            return P(None, dp, None, None, None)
        if path.endswith("/vr") or path.endswith("xvr"):
            # [count, B, L, H] — mirror k's L sharding
            if kv_sharded:
                return P(None, dp, None,
                         self._model_if_div(shape[3]))
            if self.shard_cache_seq_for_mqa:
                return P(None, dp, "model", None)
            return P(None, dp, None, None)
        if path.endswith("/pos"):
            if not kv_sharded and self.shard_cache_seq_for_mqa:
                return P(None, dp, "model")
            return P(None, dp, None)
        if path.endswith("wkv"):          # [count, B, H, hd, hd]
            return P(None, dp, self._model_if_div(shape[2]), None, None)
        if path.endswith("/h"):           # rglru [count, B, dr]
            return P(None, dp, self._model_if_div(shape[2]))
        if path.endswith("conv"):         # [count, B, K-1, dr]
            return P(None, dp, None, self._model_if_div(shape[3]))
        if path.endswith("x_tm") or path.endswith("x_cm"):
            return P(None, dp, None)
        return P(*(None,) * len(shape))

    def state_shardings(self, state_shapes, batch: int, n_kv: int) -> Any:
        flat, treedef = jax.tree_util.tree_flatten_with_path(state_shapes)
        out = []
        for path, leaf in flat:
            key = "/".join(_p(p) for p in path)
            spec = self.cache_spec(key, tuple(leaf.shape), batch, n_kv)
            out.append(NamedSharding(self.mesh, spec))
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(state_shapes), out)

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())


# ---------------------------------------------------------------------------
# Graph (GCN engine) mesh.  The block-ELL aggregation shards by row-stripe
# over one mesh axis; its PartitionSpecs live with the sharded engine
# (engine/sharded.py::GraphShardingRules).
# ---------------------------------------------------------------------------

def make_graph_mesh(n_devices: Optional[int] = None,
                    axis: str = "graph") -> Mesh:
    """1-D mesh over (a prefix of) the local devices for stripe sharding."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for a {axis}={n} mesh, have {len(devs)} — run "
            f"under XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    return Mesh(np.asarray(devs[:n]), (axis,))


def _p(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    return str(p)
