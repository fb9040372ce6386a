"""Typed configuration surface for the serving stack (DESIGN.md §8, §11).

``EngineConfig`` is the single source of truth for engine construction:
every knob the engine understands is a field, validation happens once in
``__post_init__`` (construction-time, not deep inside the stack), and
``Engine(tp, tc, dp, dc, config=cfg)`` is the primary constructor path.
The legacy ``Engine(**kwargs)`` sprawl still works through a deprecation
shim that simply builds an ``EngineConfig`` from the kwargs.

``SamplingParams`` is the per-request companion (vLLM-style): everything
``submit`` used to take as loose keywords — plus a per-request ``seed`` —
travels as one value object that the scheduler carries on the Request.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

from ..core.spec_decode import TemplateBank, TreeTemplate
from ..models import KV_BLOCK
from ..models.attention import KV_DTYPES


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decode options.

    ``max_new``     tokens to generate (required by submit time; the field
                    is optional so partially-specified params can be merged
                    with a positional ``max_new``).
    ``temperature`` 0 = greedy; None = the engine default.
    ``seed``        per-request PRNG seed. None derives the request stream
                    from the engine seed and rid (the historical behaviour);
                    setting it makes the request's sampled tokens
                    reproducible independent of engine seed and batch
                    composition.
    ``tree_idx``    pins one TemplateBank template (tree engines only).
    """
    max_new: Optional[int] = None
    temperature: Optional[float] = None
    seed: Optional[int] = None
    tree_idx: Optional[int] = None

    def __post_init__(self):
        if self.max_new is not None and self.max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {self.max_new}")
        if self.temperature is not None and self.temperature < 0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}")

    def merged(self, max_new: Optional[int]) -> "SamplingParams":
        """Resolve a positional ``max_new`` against this params object.
        A params object with its own max_new wins conflicts only if the
        two agree; otherwise the ambiguity is an error."""
        if max_new is None:
            if self.max_new is None:
                raise ValueError("max_new is required: pass it positionally "
                                 "or set SamplingParams.max_new")
            return self
        if self.max_new is not None and self.max_new != max_new:
            raise ValueError(
                f"conflicting max_new: positional {max_new} vs "
                f"SamplingParams.max_new={self.max_new}")
        return dataclasses.replace(self, max_new=max_new)


@dataclasses.dataclass
class EngineConfig:
    """Engine construction knobs. Validation that used to live in
    ``Engine.__init__`` runs in ``__post_init__`` (same assert semantics —
    existing callers catch AssertionError); new range checks raise
    ValueError. Model params/configs are NOT fields — they stay positional
    on ``Engine`` so one config object can serve many model pairs."""

    mode: str = "pard"
    k: int = 8
    max_batch: int = 4
    max_len: int = 1024
    temperature: float = 0.0
    eos_id: Optional[int] = None
    seed: int = 0
    kv_block_size: int = KV_BLOCK
    kv_num_blocks: Optional[int] = None
    kv_dtype: str = "bf16"
    tree: Any = None                 # branching iterable / TreeTemplate / TemplateBank
    adaptive_tree: bool = False
    tree_ewma: float = 0.2
    tree_reselect_every: int = 4
    prefix_cache: bool = False
    prefill_chunk: int = 8
    prefill_budget: Optional[int] = None
    admit_window: int = 8
    pipelined: bool = False          # default for Engine.run()
    # -- sharded serving (DESIGN.md §11, §12) ---------------------------
    # tp > 1 or dp > 1 without an explicit mesh builds a (data=dp,
    # model=tp) host mesh; an explicit mesh must carry a "model" axis of
    # size tp and a "data" axis of size dp (when they were given) and
    # wins otherwise. dp = N serves N independent engine replicas — each
    # with its own executor, DecodeState and KV pool on its own mesh row
    # — behind the one host-side scheduler (DESIGN.md §12).
    tp: int = 1
    dp: int = 1
    mesh: Any = None                 # jax.sharding.Mesh
    # "exact" = reduction-free output-dim sharding, tokens bitwise
    # identical across mesh shapes (DESIGN.md §11). "throughput" =
    # Megatron-style row-parallel down-projections, one psum per
    # attention block / MLP, tokens match tp1 to tolerance only
    # (DESIGN.md §13).
    tp_ruleset: str = "exact"

    def __post_init__(self):
        assert self.mode in ("ar", "vsd", "pard")
        assert self.kv_dtype in KV_DTYPES, \
            f"kv_dtype must be one of {sorted(KV_DTYPES)}"
        assert self.tree is None or self.mode == "pard", \
            "tree templates apply to the PARD draft path only"
        if self.adaptive_tree:
            assert self.mode == "pard", "adaptive trees require mode='pard'"
            if self.tree is None:
                self.tree = TemplateBank.default(self.k)
            assert isinstance(self.tree, TemplateBank), \
                "adaptive_tree selects from a TemplateBank"
        if self.tree is not None and not isinstance(self.tree, TemplateBank):
            # canonical form: branching iterable / TreeTemplate -> a
            # one-template bank (what SpecDecoder normalises to anyway)
            if not isinstance(self.tree, TreeTemplate):
                self.tree = TreeTemplate.from_branching(self.tree)
            self.tree = TemplateBank.from_templates((self.tree,))
        for name in ("k", "max_batch", "max_len", "kv_block_size",
                     "prefill_chunk", "admit_window", "tree_reselect_every"):
            if getattr(self, name) < 1:
                raise ValueError(f"{name} must be >= 1, "
                                 f"got {getattr(self, name)}")
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0, "
                             f"got {self.temperature}")
        if not 0.0 < self.tree_ewma <= 1.0:
            raise ValueError(f"tree_ewma must be in (0, 1], "
                             f"got {self.tree_ewma}")
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {self.tp}")
        if self.dp < 1:
            raise ValueError(f"dp must be >= 1, got {self.dp}")
        if self.tp_ruleset not in ("exact", "throughput"):
            raise ValueError("tp_ruleset must be 'exact' or 'throughput', "
                             f"got {self.tp_ruleset!r}")
        if self.mesh is None and (self.tp > 1 or self.dp > 1):
            from ..launch import mesh as mesh_mod
            self.mesh = mesh_mod.make_host_mesh(model=self.tp, data=self.dp)
        if self.mesh is not None:
            if "model" not in self.mesh.axis_names:
                raise ValueError("the serving mesh needs a 'model' axis "
                                 f"(got axes {self.mesh.axis_names})")
            if self.tp > 1 and self.mesh.shape["model"] != self.tp:
                raise ValueError(
                    f"mesh 'model' axis has {self.mesh.shape['model']} "
                    f"devices but tp={self.tp}")
            if self.dp > 1:
                if "data" not in self.mesh.axis_names:
                    raise ValueError(
                        "dp > 1 needs a mesh with a 'data' axis "
                        f"(got axes {self.mesh.axis_names})")
                if self.mesh.shape["data"] != self.dp:
                    raise ValueError(
                        f"mesh 'data' axis has {self.mesh.shape['data']} "
                        f"devices but dp={self.dp}")

    @classmethod
    def from_args(cls, ns) -> "EngineConfig":
        """Build from an argparse namespace (repro.launch.serve and the
        benchmarks share this mapping). Missing attributes fall back to
        field defaults, so partial namespaces work; ``ns.tree`` is the CLI
        string form ("2,2,1"), normalised here."""
        tree = getattr(ns, "tree", None)
        adaptive = bool(getattr(ns, "adaptive_tree", False))
        mode = getattr(ns, "mode", "pard")
        if adaptive:
            assert mode == "pard", "--adaptive-tree requires --mode pard"
            assert tree is None, \
                "--adaptive-tree selects its own bank; drop --tree"
        elif tree is not None:
            assert mode == "pard", "--tree requires --mode pard"
            if isinstance(tree, str):
                tree = TreeTemplate.from_branching(
                    int(x) for x in tree.split(","))
        kw = {}
        for f in dataclasses.fields(cls):
            if f.name in ("tree", "mesh"):
                continue
            if hasattr(ns, f.name):
                kw[f.name] = getattr(ns, f.name)
        return cls(tree=tree, mesh=getattr(ns, "mesh", None), **kw)
