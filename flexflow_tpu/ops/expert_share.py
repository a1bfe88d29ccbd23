"""One chip's share of an expert layer whose experts are spread over
several chips (DeepSeek-V3's layer, as Moonlight-16B-A3B has it, the
Qwen2-MoE family's, as Laguna-S-2.1 has it, and ``lfm2_moe``'s, as
LFM2-8B-A1B has it: Moonlight's rule without a shared expert beside it,
and SmallThinker's: a softmax router read before attention, ReGLU
experts).

:class:`TopKRouter` scores every token against ALL ``n_router`` experts
of the layer in float32, picks the ``top_k`` largest and hands on a dense
``(batch, seq, n_router)`` array of combine weights: ``scale * score /
(sum of the selected scores + denominator_eps)`` for the selected experts,
0 elsewhere (``denominator_eps`` is 0 and absent from the program unless
the model's published code adds one: ``lfm2_moe`` 1e-6).  The score rule
(``SCORE_RULES``) is the model's:

* ``sigmoid``: each logit's sigmoid, the selection by ``score + bias``.
  The bias only selects; it is state, moved after every training step by
  ``rate * sign(mean load - load)`` and never differentiated (the
  auxiliary-loss-free balancing of arXiv:2412.19437);
* ``softmax``: the softmax over all the layer's logits, the selection by
  the score itself; no bias and no state.

:class:`HeldExperts` is told which experts it holds (``experts_held``, a
range), and computes their part of ``sum_e weight_e * E_e(x)`` for the
tokens routed to them; what the other chips' experts would add is left
out, here and wherever the result goes next.  Nothing is dropped at a
per-expert capacity: the step's (token, held expert) pairs, expert by
expert, fill one buffer of ``rows_capacity`` rows (twice the balanced
load by default) whichever experts they fall on, the three products of
each gated feed-forward run as grouped products over the ragged groups,
and the rows go back to their tokens weighted.  Pairs beyond the buffer
are counted in ``state["dropped"]``, which must read 0.  Rows move by
gathers in both passes (a token reads the rows of its own pairs, of
which it has at most ``min(top_k, experts held)``, and a row of zeros
for each it lacks): the TPU serialises scatters.

An expert is a gated feed-forward of three matrices whose gate passes
SiLU (``act`` ``silu``) or ReLU (``relu``: ReGLU, as SmallThinker's
experts); a traced layer counts ``moe.expert_act.<act>``, and what its
router read as the graph shows it: ``moe.router_input.ffn`` where the
router read the experts' own input, ``moe.router_input.block`` where it
read another tensor (SmallThinker's router reads the block's normed
input before attention, and its gates then cross the attention).

Which path the grouped products take (:func:`grouped_gated_ffn`) is
observed, never set.  On a TPU (``ops/pallas.flash_enabled``), at widths
of whole lanes and a buffer a row tile divides, the nine products of a
layer (three forward, six backward) are the ``ff_gmm`` kernels of
``ops/pallas/grouped_mm.py`` under one ``custom_vjp``: tiles that take a
group's whole matrix, results written once in the type the next
operation reads, zeros in the rows of no group.  Everywhere else they
are ``jax.lax.ragged_dot``, which XLA's TPU backend compiles to a
grouped Mosaic matmul of its own at 512 x 512 x 128 tiles (2.5 to 5
times slower at a held expert's shapes, and it leaves the rows of no
group as they were: PERF.md section 6, PR 31) and every other backend to
masked dense products.  Both round where the other does.  A traced layer
counts ``kernels.gmm.ff_gmm.<rows>x<depth>x<columns>`` or
``kernels.gmm.ragged_dot``.

Grid ('e', 'n').  Only (1, 1) is implemented: the exchange that 'e' > 1
needs (tokens to the chip that holds their expert and back) does not
exist yet, and such a grid is refused, not computed wrongly.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

from flexflow_tpu import obs
from flexflow_tpu.ops import pallas     # with the operator: ops/attention.py
from flexflow_tpu.ops.base import Op, Tensor
from flexflow_tpu.strategy import ParallelConfig


def _refuse_parts(op: Op, what: str) -> None:
    if any(p != 1 for p in op.pc.dims):
        raise ValueError(
            f"op {op.name!r}: {what} runs on the grid (1, 1) only; "
            f"{op.pc.dims} needs the expert exchange across chips, which "
            f"is not implemented")


SCORE_RULES = ("sigmoid", "softmax")


class TopKRouter(Op):
    AXIS_NAMES = ("e", "n")

    def __init__(self, name: str, pc: ParallelConfig, input: Tensor,
                 n_router: int, top_k: int, scale: float,
                 bias_update_rate: float = 1e-3, score: str = "sigmoid",
                 denominator_eps: float = 0.0):
        super().__init__(name, pc, [input])
        assert input.ndim == 3
        if score not in SCORE_RULES:
            raise ValueError(f"op {name!r}: score rule {score!r}, one of "
                             f"{SCORE_RULES}")
        self.d = input.shape[2]
        self.n_router, self.top_k = int(n_router), int(top_k)
        self.scale = float(scale)
        self.score = score
        self.bias_update_rate = float(bias_update_rate)
        self.denominator_eps = float(denominator_eps)
        self.output = Tensor(input.shape[:2] + (self.n_router,), "float32",
                             self, name)

    def init_params(self, rng) -> Dict:
        import jax

        return {"kernel": jax.nn.initializers.glorot_uniform()(
            rng, (self.d, self.n_router), "float32")}

    def init_state(self) -> Dict:
        import jax.numpy as jnp

        if self.score != "sigmoid":
            return {}
        return {"bias": jnp.zeros((self.n_router,), "float32")}

    def param_specs(self):
        from jax.sharding import PartitionSpec as P

        return {"kernel": P(None, None)}

    def output_spec(self):
        from jax.sharding import PartitionSpec as P

        return P("n", None, None)

    def validate_partitioning(self):
        super().validate_partitioning()
        _refuse_parts(self, "the router")

    def forward(self, params, state, xs: List, train: bool):
        import jax
        import jax.numpy as jnp

        (x,) = xs
        # float32 for real: at default precision the TPU would multiply
        # float32 operands in bfloat16 passes, and a near-tied sixth
        # expert flips
        logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                            params["kernel"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        if self.score == "sigmoid":
            score = jax.nn.sigmoid(logits)
            ranked = jax.lax.stop_gradient(score) + state["bias"]
        else:
            score = jax.nn.softmax(logits, axis=-1)
            ranked = jax.lax.stop_gradient(score)
        _, chosen = jax.lax.top_k(ranked, self.top_k)
        mask = jnp.sum(jax.nn.one_hot(chosen, self.n_router,
                                      dtype=jnp.float32), axis=-2)
        picked = score * mask
        scaled = self.scale * picked
        total = jnp.sum(picked, axis=-1, keepdims=True)
        if self.denominator_eps:
            total = total + self.denominator_eps
        gates = scaled / total
        if not train or self.score != "sigmoid":
            return gates, state
        load = jnp.sum(mask, axis=(0, 1))
        bias = state["bias"] + self.bias_update_rate * jnp.sign(
            jnp.mean(load) - load)
        return gates, {"bias": jax.lax.stop_gradient(bias)}

    def flops_per_sample(self) -> float:
        return 2.0 * self.output.shape[1] * self.d * self.n_router

    def param_bytes(self) -> int:
        return 4 * self.d * self.n_router


# ---------------------------------------------------------------------------
# rows to and from the buffer, by gathers in both passes


def _gather_sum(rows, slots, weights=None):
    """out[t] = sum_j weights[t, j] * rows_[slots[t, j]] in float32, the
    terms in column order, where ``rows_`` is ``rows`` with a row of zeros
    appended: the row a slot without a pair names."""
    import jax.numpy as jnp

    rows_ = jnp.concatenate(
        [rows, jnp.zeros((1,) + rows.shape[1:], rows.dtype)])
    out = None
    for j in range(slots.shape[1]):
        part = jnp.take(rows_, slots[:, j], axis=0).astype(jnp.float32)
        if weights is not None:
            part = part * weights[:, j:j + 1]
        out = part if out is None else out + part
    return out


@functools.lru_cache(maxsize=None)
def _row_moves():
    """(dispatch, combine): custom-VJP pairs whose backward passes are
    gathers like their forward ones."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def dispatch(x, row_token, slots):
        return jnp.take(x, row_token, axis=0)

    def dispatch_fwd(x, row_token, slots):
        return dispatch(x, row_token, slots), slots

    def dispatch_bwd(slots, d_rows):
        return (_gather_sum(d_rows, slots).astype(d_rows.dtype),
                None, None)

    dispatch.defvjp(dispatch_fwd, dispatch_bwd)

    @jax.custom_vjp
    def combine(y, w, row_token, row_w, slot_rows, slot_live=None):
        """out[t] = sum_j w[t, j] * y[slot_rows[t, j]]; ``row_w`` is the
        flat index into ``w`` of each buffer row's weight (-1: no pair).
        Given ``slot_live`` the sum runs over a token's live slots alone,
        each weighted by the one held column whose row it names: the same
        terms in the same order, without the zeros."""
        if slot_live is None:
            return _gather_sum(y, slot_rows, w).astype(y.dtype)
        mine = ((slot_rows[:, :, None] == slot_live[:, None, :])
                & (slot_rows < y.shape[0])[:, :, None])
        w_live = jnp.sum(jnp.where(mine, w[:, :, None], 0.0), axis=1)
        return _gather_sum(y, slot_live, w_live).astype(y.dtype)

    def combine_fwd(y, w, row_token, row_w, slot_rows, slot_live=None):
        return (combine(y, w, row_token, row_w, slot_rows, slot_live),
                (y, w, row_token, row_w, slot_rows))

    def combine_bwd(res, d_out):
        y, w, row_token, row_w, slot_rows = res
        d_rows = jnp.take(d_out, row_token, axis=0)
        w_rows = jnp.where(row_w >= 0,
                           jnp.take(w.reshape(-1), jnp.maximum(row_w, 0)),
                           0.0)
        d_y = (d_rows.astype(jnp.float32) * w_rows[:, None]).astype(y.dtype)
        d_w_rows = jnp.sum(y.astype(jnp.float32)
                           * d_rows.astype(jnp.float32), axis=-1)
        d_w_rows = jnp.where(row_w >= 0, d_w_rows, 0.0)
        d_w = jnp.take(jnp.concatenate([d_w_rows, jnp.zeros((1,))]),
                       slot_rows, axis=0).astype(w.dtype)
        return d_y, d_w, None, None, None, None

    combine.defvjp(combine_fwd, combine_bwd)
    return dispatch, combine


def route_block(pairs: int) -> int:
    """The block :func:`first_reaching` cuts ``pairs`` running counts
    into: the largest power of two whose square is at most ``pairs``, at
    least 128.  256 at the expert cells' 131 072: on the chip 128 to 512
    took 0.19-0.22 ms a search at their 10 240 to 32 768 rows, 1024 0.44
    at 32 768, whose gathered blocks leave VMEM (PERF.md section 6,
    PR 39)."""
    return max(128, 1 << (pairs.bit_length() - 1) // 2)


def first_reaching(upto, rows: int, block: int):
    """For r < ``rows``: the first index whose value in the non-decreasing
    int32 ``upto`` reaches r + 1, ``len(upto)`` where none does
    (``searchsorted(upto, r + 1)``), as two counts and no sequential
    search.  ``upto`` is cut into blocks of ``block`` (the last padded
    with int32's largest): a row's block is the number of blocks whose
    last count is under r + 1, its place in the block the number of that
    block's counts under r + 1."""
    import jax.numpy as jnp

    p = upto.shape[0]
    nb = -(-p // block)
    blocks = jnp.pad(upto, (0, nb * block - p),
                     constant_values=jnp.iinfo(jnp.int32).max
                     ).reshape(nb, block)
    want = jnp.arange(1, rows + 1, dtype=jnp.int32)[:, None]
    blk = jnp.minimum(jnp.sum(blocks[None, :, -1] < want, axis=1,
                              dtype=jnp.int32), nb - 1)
    within = jnp.sum(blocks[blk] < want, axis=1, dtype=jnp.int32)
    return blk * block + within


def live_slots(slot_rows, rows_capacity: int, k: int):
    """(tokens, k): each token's rows in the buffer in expert order, then
    ``rows_capacity`` (the row of zeros), for tokens with at most ``k``
    pairs.  No sort: a running count along the held axis ranks each pair
    among its token's, and column ``i`` takes the one slot of rank ``i``
    by a compare-and-min over (tokens, experts held, k)."""
    import jax.numpy as jnp

    live = slot_rows < rows_capacity
    rank = jnp.cumsum(live, axis=1, dtype=jnp.int32) - 1
    mine = live[:, :, None] & (rank[:, :, None]
                               == jnp.arange(k, dtype=jnp.int32))
    return jnp.min(jnp.where(mine, slot_rows[:, :, None], rows_capacity),
                   axis=1)


def route_rows(held_gates, rows_capacity: int, per_token: int):
    """Where each (token, held expert) pair with a weight above 0 goes in
    a buffer of ``rows_capacity`` rows filled expert by expert, tokens in
    order.  ``held_gates``: (tokens, experts held), at most ``per_token``
    pairs a token.  Returns ``row_token`` (rows,), ``row_w`` (rows,; flat
    index into held_gates, -1 for an empty row), ``slot_rows`` (tokens,
    experts held; the row of each pair, ``rows_capacity`` where there is
    none or it did not fit), ``slot_live`` (tokens, K = min(per_token,
    experts held); :func:`live_slots`, or ``slot_rows`` itself where K is
    every held expert), ``group_sizes`` (experts held,) and the number of
    pairs that did not fit.  A traced call counts
    ``moe.route.blocked.<blocks>x<block>`` and
    ``moe.route.slots.<K>of<experts held>``."""
    import jax.numpy as jnp

    t, e = held_gates.shape
    picked = (held_gates > 0).T.reshape(-1)            # expert by expert
    upto = jnp.cumsum(picked.astype(jnp.int32))
    total = upto[-1]
    fits = picked & (upto <= rows_capacity)
    slot_rows = jnp.where(fits, upto - 1, rows_capacity).reshape(e, t).T
    ends = jnp.minimum(upto.reshape(e, t)[:, -1], rows_capacity)
    group_sizes = jnp.diff(ends, prepend=0)
    # the pair that fills row r is the first whose running count is r + 1
    block = route_block(t * e)
    obs.count(f"moe.route.blocked.{-(-t * e // block)}x{block}")
    pair = first_reaching(upto, rows_capacity, block)
    used = jnp.arange(rows_capacity) < jnp.minimum(total, rows_capacity)
    pair = jnp.where(used, pair, 0)
    row_token, row_expert = pair % t, pair // t
    row_w = jnp.where(used, row_token * e + row_expert, -1)
    k = min(per_token, e)
    obs.count(f"moe.route.slots.{k}of{e}")
    slot_live = (slot_rows if k == e
                 else live_slots(slot_rows, rows_capacity, k))
    return (row_token, row_w, slot_rows, slot_live, group_sizes,
            jnp.maximum(total - rows_capacity, 0))


def grouped_gated_ffn(rows, group_sizes, w_gate, w_up, w_down,
                      act: str = "silu"):
    """The gated feed-forward of each group's expert on its rows, its gate
    through ``act`` (``grouped_mm.ACTIVATIONS``: SiLU or ReLU): three
    grouped products with float32 accumulation (nine with their
    backward), through the ``ff_gmm`` kernels where the backend is a TPU
    and the shapes are theirs, else ``jax.lax.ragged_dot``."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops.pallas import grouped_mm

    w_gate, w_up, w_down = (w.astype(rows.dtype)
                            for w in (w_gate, w_up, w_down))
    if pallas.flash_enabled():
        made = grouped_mm.gated_ffn(rows, group_sizes, w_gate, w_up, w_down,
                                    act=act)
        if made is not None:
            y, tiles = made
            obs.count("kernels.gmm.ff_gmm." + "x".join(map(str, tiles)))
            return y
    obs.count("kernels.gmm.ragged_dot")

    def gmm(a, w):
        return jax.lax.ragged_dot(a, w, group_sizes,
                                  preferred_element_type=jnp.float32)

    gating = grouped_mm.ACTIVATIONS[act]
    h = (gating(gmm(rows, w_gate)) * gmm(rows, w_up)).astype(rows.dtype)
    return gmm(h, w_down).astype(rows.dtype)


class HeldExperts(Op):
    AXIS_NAMES = ("e", "n")

    def __init__(self, name: str, pc: ParallelConfig, input: Tensor,
                 gates: Tensor, d_ff: int, experts_held: Tuple[int, int],
                 top_k: int, capacity_factor: float = 2.0,
                 act: str = "silu"):
        from flexflow_tpu.ops.pallas.grouped_mm import ACTIVATIONS

        super().__init__(name, pc, [input, gates])
        if act not in ACTIVATIONS:
            raise ValueError(f"op {name!r}: expert activation {act!r}, one "
                             f"of {sorted(ACTIVATIONS)}")
        self.act = act
        assert input.ndim == 3 and gates.shape[:2] == input.shape[:2]
        self.d, self.d_ff = input.shape[2], int(d_ff)
        self.n_router = gates.shape[2]
        lo, hi = (int(v) for v in experts_held)
        if not 0 <= lo < hi <= self.n_router:
            raise ValueError(f"op {name!r}: experts_held [{lo}, {hi}) is "
                             f"no range of the router's {self.n_router}")
        self.experts_held = (lo, hi)
        self.top_k = int(top_k)
        self.capacity_factor = float(capacity_factor)
        tokens = input.shape[0] * input.shape[1]
        held = hi - lo
        # capacity_factor times the balanced load, at most every pair
        self.rows_capacity = min(tokens * min(self.top_k, held), 8 * math.ceil(
            self.capacity_factor * tokens * self.top_k * held
            / self.n_router / 8))
        self.output = Tensor(input.shape, input.dtype, self, name)

    @property
    def num_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    def init_params(self, rng) -> Dict:
        import jax

        e, d, f = self.num_held, self.d, self.d_ff
        keys = jax.random.split(rng, 3)
        # glorot over one expert's matrix, not over the stack
        init = jax.nn.initializers.glorot_uniform(in_axis=-2, out_axis=-1,
                                                  batch_axis=(0,))
        return {"w_gate": init(keys[0], (e, d, f), "float32"),
                "w_up": init(keys[1], (e, d, f), "float32"),
                "w_down": init(keys[2], (e, f, d), "float32")}

    def init_state(self) -> Dict:
        import jax.numpy as jnp

        return {"counts": jnp.zeros((self.n_router,), "float32"),
                "dropped": jnp.zeros((), "float32")}

    def param_specs(self):
        from jax.sharding import PartitionSpec as P

        return {k: P("e", None, None) for k in ("w_gate", "w_up", "w_down")}

    def output_spec(self):
        from jax.sharding import PartitionSpec as P

        return P("n", None, None)

    def validate_partitioning(self):
        super().validate_partitioning()
        _refuse_parts(self, "the held experts' share")

    def forward(self, params, state, xs: List, train: bool):
        import jax
        import jax.numpy as jnp

        x, gates = xs
        b, s, d = x.shape
        lo, hi = self.experts_held
        obs.count("moe.experts_held", self.num_held, level=True)
        obs.count("moe.rows_capacity", self.rows_capacity, level=True)
        obs.count(f"moe.expert_act.{self.act}")
        router = self.inputs[1].producer
        if router is not None:
            obs.count("moe.router_input." + (
                "ffn" if router.inputs[0] is self.inputs[0] else "block"))
        g = jax.lax.stop_gradient(gates).reshape(b * s, self.n_router)
        row_token, row_w, slot_rows, slot_live, group_sizes, dropped = \
            route_rows(g[:, lo:hi], self.rows_capacity, self.top_k)
        dispatch, combine = _row_moves()
        rows = dispatch(x.reshape(b * s, d), row_token, slot_live)
        y = grouped_gated_ffn(rows, group_sizes, params["w_gate"],
                              params["w_up"], params["w_down"], self.act)
        out = combine(y, gates.reshape(b * s, self.n_router)[:, lo:hi],
                      row_token, row_w, slot_rows,
                      None if slot_live is slot_rows else slot_live)
        if train:
            state = {"counts": jnp.sum((g > 0).astype(jnp.float32), axis=0),
                     "dropped": dropped.astype(jnp.float32)}
        return out.reshape(b, s, d), state

    def state_counters(self, state: Dict) -> Dict[str, Tuple[float, str]]:
        """What ``fit`` publishes from this operator's state at its sync
        points, and how a name merges over operators: the fullest
        expert's load over the mean load (the worst layer's), and the
        pairs that did not fit the buffer (all layers')."""
        import numpy as np

        counts = np.asarray(state["counts"], np.float64)
        mean = counts.mean()
        return {"moe.load_max_over_mean":
                (float(counts.max() / mean) if mean else 0.0, "max"),
                "moe.dropped_pairs":
                (float(np.asarray(state["dropped"])), "sum")}

    def cost_signature(self) -> tuple:
        return (self.experts_held, self.n_router, self.top_k, self.d_ff,
                self.rows_capacity, self.act)

    def flops_per_sample(self) -> float:
        # the balanced load: top_k * held / n_router experts a token
        per_token = self.top_k * self.num_held / self.n_router
        return 6.0 * self.output.shape[1] * per_token * self.d * self.d_ff

    def param_bytes(self) -> int:
        return 4 * 3 * self.num_held * self.d * self.d_ff
