#!/usr/bin/env python3
"""One rehearsal run (bench/rehearse.py, CPU, tiny sizes) with a fault
planted in the program underneath the timed path:

    python bench/tests/fault_run.py <fault> --workload <name> --seed <n> --seconds <s>

Faults, by the cell kind that can have them:

* serving: ``token_altered`` (each served token replaced by the next id
  where it is produced), ``state_unchanged`` (decode steps leave the KV
  pool as it was), ``half_batch`` (the second half of the slots get the
  first half's tokens);
* training: ``state_unchanged`` (the step returns parameters and
  optimizer state as they came), ``half_batch`` (half of every chip's
  rows left out, the mean taken over the rest), ``no_exchange`` (the
  gradient stream to the reduce chip left out), ``frozen_leaf`` (one
  leaf's update never lands);
* ``none``: nothing planted.

The result line is the harness's own; the test reads ``correct``.
"""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def plant(fault: str) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.serve import engine, kvstore
    from repro.train import train_step

    if fault == "token_altered":
        orig = engine.Engine._retire

        def retire(self, next_np):
            return orig(self, (np.asarray(next_np) + 1) % self.model.cfg.vocab_size)

        engine.Engine._retire = retire
    elif fault == "state_unchanged_serve":
        def absorb_rows(self, rows_k, rows_v, active):
            for i in active:
                self.lens[i] = min(self.lens[i] + 1, self.max_len)

        kvstore.PagedKVStore.absorb_rows = absorb_rows
    elif fault == "half_batch_serve":
        orig_sample = engine.sample_last

        def sample_last(logits):
            tok = orig_sample(logits)
            h = tok.shape[0] // 2
            return tok.at[h:2 * h].set(tok[:h]) if h else tok

        engine.sample_last = sample_last
    elif fault == "state_unchanged_train":
        def apply_updates(cfg, params, grads, state):
            return params, dict(state, step=state["step"] + 1)

        train_step.apply_updates = apply_updates
    elif fault == "frozen_leaf":
        orig_apply = train_step.apply_updates

        def apply_updates(cfg, params, grads, state):
            new, st = orig_apply(cfg, params, grads, state)
            return dict(new, embed=params["embed"]), st

        train_step.apply_updates = apply_updates
    elif fault == "no_exchange":
        from repro.core import channel

        def stream_fold_tree(self, payload, **kw):
            import jax

            return jax.tree.map(lambda x: x.astype(jnp.float32), payload)

        channel.StreamChannel.stream_fold_tree = stream_fold_tree
    elif fault == "half_batch_train":
        orig_make = train_step.make_jitted_step

        def make_jitted_step(*a, **kw):
            step, in_sh = orig_make(*a, **kw)

            def halved(params, opt_state, batch):
                m = np.asarray(batch["mask"]).copy()
                m[1::2] = 0.0  # every other row of each chip's block
                return step(params, opt_state, dict(batch, mask=jnp.asarray(m)))

            return halved, in_sh

        train_step.make_jitted_step = make_jitted_step
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


def main() -> int:
    fault, argv = sys.argv[1], sys.argv[2:]
    sys.path.insert(0, str(ROOT / "bench"))
    import rehearse

    return rehearse.main(argv, before=lambda: plant(fault))


if __name__ == "__main__":
    sys.exit(main())
