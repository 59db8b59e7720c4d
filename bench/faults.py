"""Faults planted in the program under test, to show that the check fails
them: ``plant(name)`` patches the program before a cell is set up.

  token       the gateway's decode step returns every token plus one;
  state       the train step returns its weights and optimizer state
              unchanged;
  half        the train step's loss leaves out the second half of each
              data shard's rows and takes the mean over the rest;
  exchange    the train step leaves out the gradient exchange between
              data-parallel chips.
"""
from __future__ import annotations


def plant(name: str):
    if name == "token":
        from repro.frontend import gateway
        real_decode = gateway.Gateway._decode_fn

        def bad_decode(self, carry, pos):
            tok, cache = real_decode(self, carry, pos)
            return (tok + 1) % self.dec.model.cfg.vocab, cache
        gateway.Gateway._decode_fn = bad_decode
    elif name == "state":
        from repro.optim import optimizers
        real_update = optimizers.update

        def frozen(grads, state, params, oc):
            _, _, m = real_update(grads, state, params, oc)
            return params, state, m
        optimizers.update = frozen
    elif name == "half":
        from repro.models import model
        real_loss = model.LM.loss

        def half_loss(self, params, batch):
            n = batch["tokens"].shape[0] // 2
            return real_loss(self, params,
                             {k: v[:n] for k, v in batch.items()})
        model.LM.loss = half_loss
    elif name == "exchange":
        from repro.core import overlap
        overlap.exchange_phylanx = lambda grads, *a, **k: grads
    elif name:
        raise SystemExit(f"unknown fault {name!r}")
