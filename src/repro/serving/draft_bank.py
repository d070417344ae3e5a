"""Draft-level bank: a §4.1 DSIA hierarchy materialized into executable
batched levels for the ``cascade_fused`` serving mode.

``dsia.build_hierarchy`` describes a hierarchy *symbolically* (DraftSpec
per level: gates / quantize / attn_override + App. D cold-start priors).
The bank turns each neural level into something the batched runtime can
dispatch directly:

  - **gates** — the per-layer 0/1 vector as a device-ready float array
    (layer-sparsity / early-exit levels share the target's params and
    executable, exactly like ``chain_fused``/``tree_fused`` drafting);
  - **int8 levels** — own a copy of the dense-MLP weights, made ONCE at
    bank build; every other leaf is the target's own object. Execution is
    backend-aware. On TPU the copy is each MLP weight's int8 image
    (``models.model.int8_mlp_params``: int8 (R, K, N) plus float32
    (R, 1, N) scales, per layer and column over the whole K) and the
    level sets ``quantize="int8"`` on its decode calls, which
    routes the MLP matmuls through the Pallas ``kernels.quantized_matmul``
    W8A8 kernel: only the activations are quantized per call. Off-TPU the
    kernel would run interpreted (orders of magnitude slower than XLA),
    so the copy is the dequantized image instead
    (``engine.fake_quant_int8``) — the CPU numerics simulation of the
    same contract (``tests/test_int8_parity.py`` pins the two paths
    together). ``param_bytes`` reports the bytes of the copy;
  - **attn_override** — StreamingAttention levels carry the override dict
    that ``models.model.decode_step`` applies to full-attention layers.

Level order follows the hierarchy: ``levels[0]`` is the strongest (closest
to the target), ``levels[-1]`` the cheapest — the cascade drafter. The
retrieval bottom (PLD) is kept as ``bank.pld`` for priors; it never
executes on device.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import jax
import numpy as np

from repro.config.base import ModelConfig
from repro.core.dsia import DraftSpec, PLD_SPEC
from repro.core.engine import fake_quant_int8
from repro.models import model as M


@dataclasses.dataclass(frozen=True)
class DraftLevel:
    """One executable cascade level (see module docstring)."""
    index: int                       # 0 = strongest, len-1 = cheapest/drafter
    spec: DraftSpec
    params: dict                     # executable params (shared or int8 copy)
    gates: Optional[np.ndarray]      # (num_layers,) f32, None = all layers on
    quantize: Optional[str]          # "int8" -> W8A8 kernel path at decode
    attn_override: Optional[dict]    # {"kind","window","sink"} or None
    owns_params: bool                # True iff ``params`` holds an int8 copy

    @property
    def name(self) -> str:
        return self.spec.name


class DraftBank:
    """Materialized DSIA hierarchy + per-(level, slot) tracker key schema.

    ``int8_exec`` picks the ActivationQuant execution:
      - ``"auto"``   — kernel on TPU, fake-quant simulation elsewhere;
      - ``"kernel"`` — force the Pallas W8A8 path (interpret-mode off TPU;
        only sensible in parity tests);
      - ``"sim"``    — force the fake-quant parameter copy.

    The int8 copy is placed like the target weights it was made from
    (``quantize_weight`` reads each weight's sharding), so sharded servers
    keep every cascade level tensor-parallel on the same mesh.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: dict,
        hierarchy: Sequence[DraftSpec],
        *,
        int8_exec: str = "auto",
    ):
        if int8_exec not in ("auto", "kernel", "sim"):
            raise ValueError(f"unknown int8_exec {int8_exec!r}")
        if int8_exec == "auto":
            int8_exec = "kernel" if jax.default_backend() == "tpu" else "sim"
        self.cfg = cfg
        neural = [s for s in hierarchy if s.kind == "neural"]
        retrieval = [s for s in hierarchy if s.kind == "retrieval"]
        if not neural:
            raise ValueError("hierarchy has no neural level to execute")
        self.pld: DraftSpec = retrieval[0] if retrieval else PLD_SPEC
        self.levels: List[DraftLevel] = []
        int8_params: Optional[dict] = None   # one int8 copy, shared
        for i, spec in enumerate(neural):
            gates = None
            if spec.gates is not None:
                gates = spec.gates_array(cfg.num_layers)
            level_params, quantize = params, None
            if spec.quantize is not None:
                if spec.quantize != "int8":
                    raise ValueError(
                        f"level {spec.name!r}: unsupported quantize "
                        f"{spec.quantize!r} (only 'int8')"
                    )
                if int8_params is None:
                    int8_params = (
                        M.int8_mlp_params(params)
                        if int8_exec == "kernel" else fake_quant_int8(params)
                    )
                level_params = int8_params
                if int8_exec == "kernel":
                    quantize = "int8"
            override = None
            if spec.attn_override is not None:
                kind, window, sink = spec.attn_override
                override = {"kind": kind, "window": window, "sink": sink}
            self.levels.append(DraftLevel(
                index=i, spec=spec, params=level_params, gates=gates,
                quantize=quantize, attn_override=override,
                owns_params=level_params is not params,
            ))
        shared = {id(leaf) for leaf in jax.tree.leaves(params)}
        self.param_bytes = sum(
            leaf.nbytes for leaf in jax.tree.leaves(int8_params)
            if id(leaf) not in shared
        )

    # ------------------------------------------------------------- accessors
    def __len__(self) -> int:
        return len(self.levels)

    @property
    def drafter(self) -> DraftLevel:
        """The cheapest level — runs the drafting scan."""
        return self.levels[-1]

    @property
    def rescorers(self) -> List[DraftLevel]:
        """Stronger levels in rescore order: just-above-drafter first, the
        strongest (target-adjacent) level last."""
        return self.levels[-2::-1]

    # ------------------------------------------------- tracker key schema
    def slot_key(self, level: int, slot: int) -> str:
        """Acceptance key for (level, slot): level 0's alpha prices target
        acceptance of the strongest level's tokens; level i>0's alpha prices
        level i-1's acceptance of level i's tokens."""
        return f"casc{level}:{slot}"

    def direct_key(self, slot: int) -> str:
        """Acceptance of the CHEAPEST level's tokens directly by the target
        (observed only on rounds routed single-level — prices the
        no-rescore plan in ``latency.best_cascade_plan``)."""
        return f"cascdir:{slot}"

    def cost_key(self, level: int) -> str:
        return f"casc_rescore:{self.levels[level].name}"

    # ------------------------------------------------------- App. D priors
    def alpha_prior(self, level: int) -> float:
        """Cold-start acceptance prior for ``slot_key(level, ·)``."""
        spec = self.levels[level].spec
        if level == 0:
            return float(spec.prior_alpha)
        return spec.prior_alpha_given(self.levels[level - 1].spec)

    def direct_prior(self) -> float:
        """Compositional cold-start prior for the cheapest-vs-target plan."""
        p = 1.0
        for i in range(len(self.levels)):
            p *= self.alpha_prior(i)
        return float(p)

    def c_prior(self, level: int) -> float:
        return float(self.levels[level].spec.prior_c)
