"""The ``FinetuneMethod`` strategy protocol.

A fine-tuning method owns everything the paper varies between its compared
approaches: what state a training run carries, how one optimization step is
built, which parameters an evaluation should use, and how many parameters /
optimizer bytes the method actually trains. The trainer is method-agnostic:
it only drives data, logging, checkpointing, and the straggler watchdog.

Implementations are registered in ``repro.methods.registry`` under a string
key; ``registry.build(name, train_cfg)`` resolves a ready-to-use instance.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Protocol, runtime_checkable

from repro.configs.base import ModelConfig, OptimizerConfig


@dataclass(frozen=True)
class TrainableReport:
    """What a method actually trains (paper §3.3 memory model surface).

    ``opt_bytes`` is the deterministic §3.3 model (2 * P_selected * B);
    ``opt_bytes_resident`` is the *measured* accelerator-resident bytes of
    the actual ``state["opt"]`` pytree (host-resident leaves excluded) —
    equal to the full m/v footprint under dense residency, and only the
    compact [k]-slot banks under banked residency."""

    method: str
    num_params_total: int      # all model parameters
    num_params_trainable: int  # parameters the method may update per run
    opt_bytes: int             # modeled optimizer-state bytes (m + v)
    detail: str = ""
    opt_bytes_resident: int = -1  # measured device-resident bytes (-1 = n/a)

    @property
    def trainable_fraction(self) -> float:
        return self.num_params_trainable / max(1, self.num_params_total)


@runtime_checkable
class FinetuneMethod(Protocol):
    """Strategy interface every registered method implements."""

    name: str

    def init_state(self, model_cfg: ModelConfig, opt_cfg: OptimizerConfig,
                   seed: int = 0, mesh=None) -> dict:
        """Fresh TrainState pytree (params + optimizer + method state).

        ``mesh`` is forwarded when the trainer runs data-parallel; methods
        whose state layout depends on the mesh (the banked full store under
        ``offload == "zero1"`` shards 1/dp over the data axis) use it at
        init, everything else may ignore it.

        For the masked-selection family, ``state["opt"]`` follows
        ``opt_cfg.moment_residency``:

        * ``"device"``: ``{"m", "v", "counts"}`` — full-shape f32 moments
          congruent with params plus per-block bias-correction counts.
        * ``"banked"``: ``{"banks", "slot_map", "counts", "store"}`` —
          per-group compact moment banks ``{"m", "v", "slots"}`` with
          leading axis min(group length, k); ``slot_map`` [num_blocks] i32
          (block -> bank slot, -1 = host-resident, numpy, never enters
          jit); ``store`` the full-shape backing store (numpy host arrays
          under ``opt_cfg.offload == "host"``, device arrays otherwise).
          See core/masked_adamw.init_banked_opt_state for the contract.
        """
        ...

    def make_step(self, model_cfg: ModelConfig, opt_cfg: OptimizerConfig, *,
                  mesh=None, batch_axes=("data",), donate: bool = True,
                  state_shardings=None):
        """-> jitted ``(state, batch) -> (state, metrics)``.

        Whether the step runs the Pallas kernels is the model's decision
        (``kernels.ops.use_kernels(model_cfg)``), not the caller's.

        ``state_shardings`` (the method's ``state_shardings()`` tree, passed
        by the trainer when a mesh is active) lets the step pin its state
        outputs to the input layout so data-parallel steps stay
        compile-once; methods without sharded state may ignore it."""
        ...

    def eval_params(self, model_cfg: ModelConfig, opt_cfg: OptimizerConfig,
                    state: dict) -> dict:
        """Inference-ready parameter pytree for the current state."""
        ...

    def trainable_param_report(self, model_cfg: ModelConfig,
                               state: dict) -> TrainableReport:
        """Trainable-parameter / optimizer-memory accounting."""
        ...
