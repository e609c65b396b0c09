"""CyclicPruningHarness — repeated LR re-warming within a sparsity level.

Reference: /root/reference/harness_definitions/cyclic_harness.py:25-299 —
identical to the standard harness except ``train_one_level`` splits the
epoch budget across ``num_cycles`` cycles (8 split strategies,
harness_utils.py:159-245) and re-creates optimizer + schedule each cycle
(cyclic_harness.py:193-194), logging a ``cycle`` column. The reference's
call into its schedule generator is broken for num_cycles>1
(cyclic_harness.py:175 passes kwargs the function doesn't take — SURVEY.md
§2.1); here the signature actually matches.
"""

from __future__ import annotations

from ..config.schema import ConfigError
from ..pruning import generate_cyclical_schedule
from ..utils import MODEL_INIT, OPTIMIZER_INIT, tracing
from ..utils.experiment import display_training_info
from .pruning_harness import PruningHarness


class CyclicPruningHarness(PruningHarness):
    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        if self.cfg.experiment_params.checkpoint_every_epochs:
            # The cyclic level loop below fully overrides the base harness's
            # and has no mid-level re-entry: accepting the knob would
            # silently provide NO preemption protection.
            raise ConfigError(
                "experiment_params.checkpoint_every_epochs > 0 is not "
                "supported with cyclic training — the cyclic loop cannot "
                "resume mid-level, so the setting would be a silent no-op. "
                "Set checkpoint_every_epochs=0 (level-granular resume still "
                "works)."
            )

    def epochs_in_level(self) -> int:
        """The cycles' epochs: the split may leave some of the budget out."""
        ct = self.cfg.cyclic_training
        return sum(
            generate_cyclical_schedule(
                self.cfg.experiment_params.epochs_per_level, ct.num_cycles, ct.strategy
            )
        )

    def train_one_level(
        self, epochs_per_level: int, level: int, num_cycles: int = 0
    ) -> dict:
        ct = self.cfg.cyclic_training
        num_cycles = num_cycles or ct.num_cycles
        cycle_epochs = generate_cyclical_schedule(
            epochs_per_level, num_cycles, ct.strategy
        )
        max_test_acc = 0.0
        for cycle, epochs in enumerate(cycle_epochs):
            # Fresh optimizer + schedule per cycle: the LR re-warms from the
            # schedule's start (cyclic_harness.py:180-194). setup_level
            # re-inits the optimizer from FULL params, so the execution plan
            # enters/exits per cycle — the planned step bundle is cached by
            # (total_steps, widths, nm signature) and cycles with equal
            # epoch budgets reuse one executable.
            with tracing.span("level/setup", cycle=cycle):
                self.setup_level(epochs)
                if cycle == 0:
                    density = self.mask_count().density
                    display_training_info(self.cfg, level, density)
                    if level == 0:
                        # Saved BEFORE any training so cycle-0 state is the
                        # true init (reference saves inside the first cycle,
                        # cyclic_harness.py:202-211), with the fresh
                        # opt_state pytree setup_level just made.
                        self.ckpts.save_model(MODEL_INIT, self.state)
                        self.ckpts.save_optimizer(
                            OPTIMIZER_INIT, self.state.opt_state
                        )
                    self.maybe_rewind_optimizer(level)
                self._enter_plan()
            if level == 1:
                tracing.stop_profile()  # driver.run's session over the 0 -> 1 boundary
            try:
                for epoch in range(epochs):
                    with self._epoch_scope(level, epoch, cycle=cycle):
                        max_test_acc = self._train_eval_log(
                            {"level": level, "cycle": cycle, "epoch": epoch},
                            max_test_acc,
                        )
                        if level == 0 and cycle == 0:
                            self._maybe_save_rewind_point(epoch)
            finally:
                self._exit_plan()

        with tracing.span("level/finish"):
            return self.metrics.finish_level(
                level,
                {
                    "density": density,
                    "final_sparsity": self.mask_count().sparsity,
                    "num_cycles": num_cycles,
                },
            )

    def _log_console(self, row: dict) -> None:
        cyc = row.get("cycle", 0)
        print(
            f"[L{row['level']:>2} C{cyc} E{row['epoch']:>3}] "
            f"train {row['train_loss']:.4f}/{row['train_acc']:5.2f}% "
            f"test {row['test_loss']:.4f}/{row['test_acc']:5.2f}% "
            f"sparsity {row['sparsity']:5.2f}%",
            flush=True,
        )
