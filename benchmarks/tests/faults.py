"""The timed path broken underneath, one context manager for each fault
a cell can have. ``test_faults.py`` drives the rest of a run over each at
a size the CPU holds; ``fault_on_chip.py`` does the same at a cell's own
size on the chip. (One chip: there is no exchange between chips to leave
out.)"""

import contextlib

import jax.numpy as jnp


@contextlib.contextmanager
def _patched(owner, name, new):
    real = getattr(owner, name)
    setattr(owner, name, new(real))
    try:
        yield
    finally:
        setattr(owner, name, real)


def token_altered():
    """Every eighth token of a request is another token, altered where
    the engine emits it (so the engine decodes on from the altered one)."""
    from paddle_tpu.serving.engine import ServingEngine

    def new(real):
        def emit(self, req, token, events):
            if len(req.tokens) % 8 == 7:
                token = (token + 1) % self.model.config.vocab_size
            return real(self, req, token, events)
        return emit

    return _patched(ServingEngine, "_emit", new)


def request_cut_short():
    """The engine gives up on a request after three tokens."""
    from paddle_tpu.serving.engine import ServingEngine

    def new(real):
        def emit(self, req, token, events):
            if len(req.tokens) == 2 and req.max_new_tokens > 4:
                req.max_new_tokens = 3
            return real(self, req, token, events)
        return emit

    return _patched(ServingEngine, "_emit", new)


def state_unchanged():
    """A train step that returns its loss and leaves weights and master
    weights as they were."""
    import paddle_tpu as pt

    def new(real):
        def call(self, *batch):
            params = {k: jnp.copy(v) for k, v in
                      self.model.param_dict(trainable_only=True).items()}
            master = (None if self._opt_state is None else
                      {k: None if v is None else jnp.copy(v)
                       for k, v in self._opt_state["master"].items()})
            loss = real(self, *batch)
            self.model.set_state_dict(params)        # weights as they were
            if master is None:
                master = {k: v.astype(jnp.float32)
                          for k, v in params.items()}
            self._opt_state["master"] = master
            return loss
        return call

    return _patched(pt.jit.TrainStep, "__call__", new)


def half_batch():
    """Half of the batch left out, the mean taken over the rest."""
    from paddle_tpu.models.llama import LlamaForCausalLM

    def new(real):
        def loss(self, logits, labels, ignore_index=-100):
            half = logits.shape[1] // 2
            return real(self, logits[:, :half], labels[:, :half],
                        ignore_index)
        return loss

    return _patched(LlamaForCausalLM, "loss", new)


FAULTS = {"token_altered": token_altered,
          "request_cut_short": request_cut_short,
          "state_unchanged": state_unchanged, "half_batch": half_batch}
