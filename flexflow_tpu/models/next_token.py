"""What the causal language models built from a public ``config.json``
share (``models/latent_moe.py``, ``models/hybrid_ssm.py``): the next-token
loss over the graph's ``loss_op``, the plain-SGD step and its optimizer
state.  A subclass builds its graph and sets ``self.t`` (with
``batch_size``, ``seq_length``, ``learning_rate``), ``self.tokens``,
``self.labels`` and ``self.loss_op``.
"""

from __future__ import annotations

from typing import Dict

from flexflow_tpu.model import FFModel


def sgd_settings(config: Dict) -> Dict:
    """``{"learning_rate": ..}`` (or nothing) from a configuration file's
    ``optimizer``: the token models train under plain SGD without weight
    decay, the only step ``FFModel.make_sgd_step`` has."""
    opt = config.get("optimizer", {})
    if opt.get("kind", "sgd") != "sgd" or opt.get("weight_decay", 0.0):
        raise ValueError("token models train under plain SGD without "
                         "weight decay (FFModel.make_sgd_step)")
    return ({"learning_rate": float(opt["learning_rate"])}
            if "learning_rate" in opt else {})


class NextTokenLM(FFModel):
    def loss_fn(self, params, state, tokens, labels, train: bool = True):
        """Mean next-token cross-entropy: position i predicts
        ``labels[i + 1]`` and the last position has no target, as
        ``TransformerLM.loss_fn`` shifts them.  No balance loss."""
        import jax.numpy as jnp

        labels = jnp.concatenate(
            [labels[:, 1:],
             jnp.full((labels.shape[0], 1), -1, labels.dtype)], axis=1)
        inputs = {self.tokens.tid: tokens, self.labels.tid: labels}
        values, new_state = self.apply(params, state, inputs, train)
        op = self.loss_op
        xs = (values[op.output.tid], values[op.labels_tensor.tid])
        with self._op_scope(op, xs):
            total = op.loss(*xs)
        return total / (self.t.batch_size * (self.t.seq_length - 1)), \
            new_state

    def make_train_step(self):
        return self.make_sgd_step(self.t.learning_rate)

    def init_opt_state(self, params):
        return self.master_opt_state(params)
