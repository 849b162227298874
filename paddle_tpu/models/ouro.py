"""Ouro (Zhu et al. 2025, "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741; model type `ouro`,
https://huggingface.co/ByteDance/Ouro-2.6B): a decoder-only LM whose stack
of L layers runs `total_ut_steps` times over ONE set of weights, with an
exit gate that spreads every token's loss over the steps.

  layer     x += rms_2(Attn(rms_1(x))); x += rms_4(MLP(rms_3(x))): a norm
            before and after each operator, eps 1e-6, no bias anywhere.
            Attn is the shared `transformer.multi_head_attention` (causal,
            fused, plain multi-head, RoPE over the whole head); MLP is
            W_down(silu(W_gate h) * W_up h).
  step t    x_t = rms_f(Layers(x_{t-1})), x_0 the embedding: the same L
            layers and the same final norm every time (the published
            modeling_ouro.py applies `norm` inside the loop); positions do
            not change between steps.
  head      l_t[n] = CE(W_head x_t[n], y[n]): one untied head, run once
            over the steps' rows stacked (along the time axis).
  gate      lambda_t[n] = sigmoid(w_g . x_t[n] + b_g), in float32.
            S_1 = 1, S_t = prod_{j<t} (1 - lambda_j); q_t = lambda_t S_t
            for t < T, q_T = S_T: the last step takes what is left, so
            lambda_T is never computed.
  loss      mean_n [ sum_t q_t[n] l_t[n] - beta H(q[n]) ], H the entropy
            of q[n]: the paper's first-stage objective under a uniform
            prior.  Nothing is detached.

In the Program the loop is unrolled: every step builds the L layers again
under `name_scope("ut<t>")` with the parameter names of the first, so the
Program holds L sets of layer weights, each read `total_ut_steps` times
and updated once; the head, the gate and the loss are built under
`name_scope("exit")`.  At `total_ut_steps` 1 the model is a plain stack
whose loss is the cross-entropy.  A training step leaves mean_n q_t in the
persistable [total_ut_steps] `ouro_exit_step_mean`, and adds it to
`ouro_exit_step_mean_early` ([total_ut_steps + 1], the last element the
count) while that holds fewer than 64 steps; an `is_test` program leaves
its own (`..._eval`) and, in `ouro_eval_rows` [B, 2 total_ut_steps,
T], every token's cost after each loop step and the logarithm of its exit
distribution: which step a token would leave at, and at what loss.

The train-program plumbing is `decoder.lm_train_program`;
`ouro_reference.py` is the plain float32 statement of the same equations.
"""

from .. import framework, layers
from ..initializer import Constant, Normal
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr
from . import transformer as tfm
from .decoder import lm_train_program, swiglu_mlp

__all__ = ["OuroConfig", "ouro_lm", "ouro_token_cost", "ouro_lm_program"]

EXIT_STAT = "ouro_exit_step_mean"
EXIT_STAT_EARLY, EXIT_STAT_STEPS = "ouro_exit_step_mean_early", 64
EVAL_ROWS = "ouro_eval_rows"


class OuroConfig:
    """Ouro-2.6B under the keys of its published config.json; subclass to
    shrink for tests or to cut the depth."""

    vocab_size = 49152
    hidden_size = 2048
    intermediate_size = 5632
    num_hidden_layers = 48
    num_attention_heads = 16
    num_key_value_heads = 16
    head_dim = 128
    rms_norm_eps = 1e-6
    rope_theta = 1000000.0
    max_position_embeddings = 65536
    total_ut_steps = 4
    early_exit_threshold = 1.0  # an inference key: training never exits
    # the weight of the entropy term: the paper's starting value;
    # config.json has no key for it
    exit_entropy_beta = 0.1
    partition_family = "gpt2"


def _layer(x, hp, i, is_test):
    """Layer i; its weights are named by i alone, so every loop step's
    layer i is the same weights."""

    def weight(base, initializer=Normal(0.0, 0.02)):
        return ParamAttr(name="ouro_l%d.%s" % (i, base),
                         initializer=initializer)

    def norm(h, base):  # no initializer: rms_norm's own, a weight of 1
        return layers.rms_norm(h, hp.rms_norm_eps,
                               param_attr=weight(base, None))

    d, f = hp.hidden_size, hp.intermediate_size
    h = norm(x, "attn_norm.w")
    a = tfm.multi_head_attention(
        h, h, h, None, d, hp.num_attention_heads,
        is_test=is_test, fused=True, causal=True,
        n_kv_head=hp.num_key_value_heads, rotary=True,
        rotary_base=float(hp.rope_theta), param_attr=weight)
    x = layers.elementwise_add(x, norm(a, "attn_post_norm.w"))
    h = norm(x, "ffn_norm.w")
    m = swiglu_mlp(h, f, d, "ffn", weight)
    return layers.elementwise_add(x, norm(m, "ffn_post_norm.w"))


def ouro_lm(ids, hp=OuroConfig, is_test=False):
    """[B, T] token ids -> [x_1 .. x_T_ut], each [B, T, hidden]: what the
    next step, the head and the gate read after each loop step."""
    if hp.head_dim * hp.num_attention_heads != hp.hidden_size:
        raise NotImplementedError(
            "head_dim %d x %d heads is not hidden_size %d: the attention "
            "builder splits the projection evenly"
            % (hp.head_dim, hp.num_attention_heads, hp.hidden_size))
    x = layers.embedding(
        ids, size=[hp.vocab_size, hp.hidden_size],
        param_attr=ParamAttr(name="ouro_emb.w",
                             initializer=Normal(0.0, 0.02)))
    steps = []
    for t in range(1, hp.total_ut_steps + 1):
        with framework.name_scope("ut%d" % t):
            for i in range(hp.num_hidden_layers):
                x = _layer(x, hp, i, is_test)
            x = layers.rms_norm(x, hp.rms_norm_eps,
                                param_attr=ParamAttr(name="ouro_norm.w"))
        steps.append(x)
    return steps


def _exit_distribution(steps, hp):
    """log q [B, T_ut, T] from the steps' hidden states, in float32 and in
    logarithms throughout (a saturated gate gives a q of 0, never a NaN):
    log q_t = log lambda_t + sum_{j<t} log(1 - lambda_j) for t < T_ut,
    log q_T = sum_{j<T} log(1 - lambda_j)."""
    n_ut, seq = len(steps), int(steps[0].shape[1])
    helper = LayerHelper("ouro_exit_gate")
    # a zero gate: q starts at 1/2, 1/4, ... whatever the trunk holds
    w_g = helper.create_parameter(
        ParamAttr(name="ouro_exit_gate.w", initializer=Constant(0.0)),
        shape=[hp.hidden_size], dtype="float32")
    b_g = helper.create_parameter(
        ParamAttr(name="ouro_exit_gate.b", initializer=Constant(0.0)),
        shape=[1], dtype="float32", is_bias=True)
    # the last step's gate is never read: T_ut - 1 steps' rows.  A
    # multiply and a sum over hidden, not a matmul: the AMP pass narrows
    # matmuls, and a 1-wide product earns nothing on the MXU
    x = layers.concat(steps[:-1], axis=1)  # [B, (T_ut - 1) T, d]
    z = layers.reduce_sum(layers.elementwise_mul(x, w_g), dim=-1)
    z = layers.reshape(layers.elementwise_add(z, b_g), [-1, n_ut - 1, seq])
    log_exit = layers.logsigmoid(z)                       # log lambda_t
    log_stay = layers.logsigmoid(layers.scale(z, -1.0))   # log(1-lambda_t)
    before = layers.cumsum(log_stay, axis=1, exclusive=True)  # log S_t
    return layers.concat(
        [layers.elementwise_add(log_exit, before),
         layers.reduce_sum(log_stay, dim=1, keep_dim=True)], axis=1)


def ouro_token_cost(steps, labels, hp=OuroConfig, is_test=False):
    """The steps' hidden states and [B, T] labels -> the [B, T, 1] cost of
    every token: sum_t q_t l_t - beta H(q)."""
    n_ut, seq = len(steps), int(steps[0].shape[1])
    # the head once, over the steps' rows stacked along the time axis (the
    # batch may be unknown when the program is built): one weight gradient
    rows = layers.concat(steps, axis=1) if n_ut > 1 else steps[0]
    labels = layers.unsqueeze(labels, [2])
    logits = layers.fc(
        rows, size=hp.vocab_size, num_flatten_dims=2, bias_attr=False,
        param_attr=ParamAttr(name="ouro_head.w",
                             initializer=Normal(0.0, 0.02)))
    step_cost = layers.softmax_with_cross_entropy(
        logits, layers.concat([labels] * n_ut, axis=1) if n_ut > 1
        else labels)  # [B, T_ut T, 1]
    if n_ut == 1:
        return step_cost  # q_1 = 1 and H = 0: a plain stack's loss
    log_q = _exit_distribution(steps, hp)
    q = layers.exp(log_q)
    # -beta H(q) = beta sum_t q_t log q_t
    per_step = layers.elementwise_add(
        layers.reshape(step_cost, [-1, n_ut, seq]),
        layers.scale(log_q, float(hp.exit_entropy_beta)))
    cost = layers.reduce_sum(layers.elementwise_mul(q, per_step), dim=1)

    def kept(name, shape, zeroed=False):
        """A float32 variable that stays in the scope after the step."""
        var = LayerHelper(name).create_global_variable(
            name=name, persistable=True, dtype="float32", shape=shape)
        var.stop_gradient = True
        if zeroed:
            LayerHelper(name).set_variable_initializer(var, Constant(0.0))
        return var

    q_mean = layers.reduce_mean(q, dim=[0, 2])
    layers.assign(q_mean, output=kept(
        EXIT_STAT + ("_eval" if is_test else ""), [n_ut], zeroed=True))
    if is_test:
        # what an evaluation wants of every token: the cost after each
        # loop step, then log q of each step
        layers.assign(layers.concat(
            [layers.reshape(step_cost, [-1, n_ut, seq]), log_q], axis=1),
            output=kept(EVAL_ROWS, [-1, 2 * n_ut, seq]))
    else:
        # mean_n q_t summed over the first EXIT_STAT_STEPS steps of a run,
        # and how many steps that was: what a run reads the same whenever
        # it looks (a step's own q_1 swings 0.26 .. 0.58 under Adam at 4e-4)
        early = kept(EXIT_STAT_EARLY, [n_ut + 1], zeroed=True)
        live = layers.cast(layers.less_than(
            layers.slice(early, [0], [n_ut], [n_ut + 1]),
            layers.fill_constant([1], "float32", float(EXIT_STAT_STEPS))),
            "float32")
        step = layers.concat(
            [q_mean, layers.fill_constant([1], "float32", 1.0)], axis=0)
        layers.assign(layers.elementwise_add(
            early, layers.elementwise_mul(step, live)), output=early)
    return layers.unsqueeze(cost, [2])


def ouro_lm_program(hp=OuroConfig, seq_len=4096, lr=4e-4, is_test=False,
                    use_bf16=False, mesh=None):
    """(main, startup, feeds, [loss, token_count]) as gpt2_lm_program
    returns them; the loss is the expected loss over the exit steps less
    beta times the exit distribution's entropy."""

    def build(ids, labels):
        steps = ouro_lm(ids, hp, is_test)
        with framework.name_scope("exit"):
            return ouro_token_cost(steps, labels, hp, is_test), None

    return lm_train_program(build, seq_len, lr, is_test,
                            use_bf16, mesh, hp.partition_family)
