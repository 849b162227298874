"""Optimizers (python/paddle/fluid/optimizer.py analog).

``Optimizer.minimize`` (optimizer.py:294 parity) = append_backward +
regularization + gradient clip + per-parameter optimizer ops
(_create_optimization_pass :197).  The emitted ops compile into the same XLA
executable as forward/backward, so the whole training step is one fused TPU
program.
"""

import numpy as np

from . import framework, unique_name
from .backward import append_backward
from .framework import Variable
from .initializer import Constant
from .layer_helper import LayerHelper
from .profiler import phase

__all__ = [
    "SGD",
    "Momentum",
    "LarsMomentum",
    "Adagrad",
    "Adam",
    "Adamax",
    "DecayedAdagrad",
    "Adadelta",
    "RMSProp",
    "Ftrl",
    "ModelAverage",
    "GradientMergeOptimizer",
    "SGDOptimizer",
    "MomentumOptimizer",
    "LarsMomentumOptimizer",
    "AdagradOptimizer",
    "AdamOptimizer",
    "AdamaxOptimizer",
    "DecayedAdagradOptimizer",
    "AdadeltaOptimizer",
    "RMSPropOptimizer",
    "FtrlOptimizer",
    "Optimizer",
]


class Optimizer:
    def __init__(self, learning_rate, regularization=None, name=None):
        self.regularization = regularization
        self._name = name
        self._learning_rate = learning_rate
        self._learning_rate_map = {}
        self._accumulators = {}  # name -> {param_name: var}
        self.helper = None
        self.type = self.__class__.__name__.lower()

    # ---- learning rate ---------------------------------------------------
    def _create_global_learning_rate(self):
        program = framework.default_main_program()
        lr = self._learning_rate_map.get(program, None)
        if lr is not None:
            return
        if isinstance(self._learning_rate, Variable):
            self._learning_rate_map[program] = self._learning_rate
            return
        from .layers import tensor

        lr_var = tensor.create_global_var(
            name=unique_name.generate("learning_rate"),
            shape=[1],
            value=float(self._learning_rate),
            dtype="float32",
            persistable=True,
        )
        self._learning_rate_map[program] = lr_var

    def _global_learning_rate(self, program=None):
        if program is None:
            program = framework.default_main_program()
        return self._learning_rate_map.get(program)

    def _create_param_lr(self, param_and_grad):
        param = param_and_grad[0]
        param_lr = (param.optimize_attr or {}).get("learning_rate", 1.0)
        if isinstance(param_lr, Variable):
            # a scheduler wrote a per-param LR variable (append_LARS):
            # use it directly (optimizer.py reference behavior)
            return param_lr
        base = self._global_learning_rate()
        if param_lr == 1.0:
            return base
        from .layers import nn

        return nn.scale(base, scale=float(param_lr))

    # ---- accumulators ----------------------------------------------------
    def _add_accumulator(self, name, param, dtype=None, fill_value=0.0, shape=None):
        if name in self._accumulators and param.name in self._accumulators[name]:
            return self._accumulators[name][param.name]
        block = framework.default_main_program().global_block()
        shape = list(shape or param.shape)
        var = block.create_var(
            name=unique_name.generate(param.name + "_" + name),
            shape=shape,
            dtype=dtype or param.dtype,
            persistable=True,
            stop_gradient=True,
        )
        sb = framework.default_startup_program().global_block()
        sv = sb.create_var(name=var.name, shape=shape, dtype=var.dtype, persistable=True)
        Constant(float(fill_value))(sv, sb)
        self._accumulators.setdefault(name, {})[param.name] = var
        return var

    def _get_accumulator(self, name, param):
        return self._accumulators[name][param.name]

    def _create_accumulators(self, block, parameters):
        pass

    def _finish_update(self, block, parameters_and_grads):
        pass

    def _append_optimize_op(self, block, param_and_grad):
        raise NotImplementedError

    # ---- driver ----------------------------------------------------------
    def _create_optimization_pass(self, parameters_and_grads):
        program = framework.default_main_program()
        block = program.global_block()
        self.helper = LayerHelper(self.__class__.__name__)
        self._create_global_learning_rate()
        self._create_accumulators(block, [p for p, g in parameters_and_grads if g is not None])
        optimize_ops = []
        for param_and_grad in parameters_and_grads:
            if param_and_grad[1] is None:
                continue
            if param_and_grad[0].trainable:
                with program._optimized_guard(list(param_and_grad)):
                    optimize_ops.append(
                        self._append_optimize_op(block, param_and_grad)
                    )
        self._finish_update(block, parameters_and_grads)
        return optimize_ops

    def backward(self, loss, startup_program=None, parameter_list=None, no_grad_set=None):
        return append_backward(loss, parameter_list, no_grad_set)

    def apply_gradients(self, params_grads):
        from . import regularizer as _reg
        from . import clip as _clip

        params_grads = _clip.append_gradient_clip_ops(params_grads)
        params_grads = _reg.append_regularization_ops(params_grads, self.regularization)
        return self._create_optimization_pass(params_grads)

    def minimize(self, loss, startup_program=None, parameter_list=None, no_grad_set=None):
        with phase("build.minimize"):
            params_grads = self.backward(loss, startup_program, parameter_list, no_grad_set)
            optimize_ops = self.apply_gradients(params_grads)
        return optimize_ops, params_grads


class SGDOptimizer(Optimizer):
    def __init__(self, learning_rate, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self.type = "sgd"

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        return block.append_op(
            "sgd",
            inputs={
                "Param": [param],
                "Grad": [grad],
                "LearningRate": [self._create_param_lr(param_and_grad)],
            },
            outputs={"ParamOut": [param]},
        )


class MomentumOptimizer(Optimizer):
    def __init__(self, learning_rate, momentum, use_nesterov=False, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self.type = "momentum"
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        velocity = self._get_accumulator("velocity", param)
        return block.append_op(
            "momentum",
            inputs={
                "Param": [param],
                "Grad": [grad],
                "Velocity": [velocity],
                "LearningRate": [self._create_param_lr(param_and_grad)],
            },
            outputs={"ParamOut": [param], "VelocityOut": [velocity]},
            attrs={"mu": self._momentum, "use_nesterov": self._use_nesterov},
        )


class LarsMomentumOptimizer(Optimizer):
    def __init__(
        self, learning_rate, momentum=0.9, lars_coeff=0.001, lars_weight_decay=0.0005, **kwargs
    ):
        super().__init__(learning_rate, **kwargs)
        self.type = "lars_momentum"
        self._momentum = momentum
        self._lars_coeff = lars_coeff
        self._lars_weight_decay = lars_weight_decay

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        velocity = self._get_accumulator("velocity", param)
        return block.append_op(
            "lars_momentum",
            inputs={
                "Param": [param],
                "Grad": [grad],
                "Velocity": [velocity],
                "LearningRate": [self._create_param_lr(param_and_grad)],
            },
            outputs={"ParamOut": [param], "VelocityOut": [velocity]},
            attrs={
                "mu": self._momentum,
                "lars_coeff": self._lars_coeff,
                "lars_weight_decay": self._lars_weight_decay,
            },
        )


class AdagradOptimizer(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self.type = "adagrad"
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        moment = self._get_accumulator("moment", param)
        return block.append_op(
            "adagrad",
            inputs={
                "Param": [param],
                "Grad": [grad],
                "Moment": [moment],
                "LearningRate": [self._create_param_lr(param_and_grad)],
            },
            outputs={"ParamOut": [param], "MomentOut": [moment]},
            attrs={"epsilon": self._epsilon},
        )


class AdamOptimizer(Optimizer):
    def __init__(
        self,
        learning_rate=0.001,
        beta1=0.9,
        beta2=0.999,
        epsilon=1e-8,
        lazy_mode=False,
        **kwargs
    ):
        super().__init__(learning_rate, **kwargs)
        self.type = "adam"
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment1", p)
            self._add_accumulator("moment2", p)
            self._add_accumulator("beta1_pow_acc", p, fill_value=self._beta1, shape=[1])
            self._add_accumulator("beta2_pow_acc", p, fill_value=self._beta2, shape=[1])

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        m1 = self._get_accumulator("moment1", param)
        m2 = self._get_accumulator("moment2", param)
        b1p = self._get_accumulator("beta1_pow_acc", param)
        b2p = self._get_accumulator("beta2_pow_acc", param)
        return block.append_op(
            "adam",
            inputs={
                "Param": [param],
                "Grad": [grad],
                "Moment1": [m1],
                "Moment2": [m2],
                "Beta1Pow": [b1p],
                "Beta2Pow": [b2p],
                "LearningRate": [self._create_param_lr(param_and_grad)],
            },
            outputs={
                "ParamOut": [param],
                "Moment1Out": [m1],
                "Moment2Out": [m2],
                "Beta1PowOut": [b1p],
                "Beta2PowOut": [b2p],
            },
            attrs={"beta1": self._beta1, "beta2": self._beta2, "epsilon": self._epsilon},
        )


class AdamaxOptimizer(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self.type = "adamax"
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)
            self._add_accumulator("inf_norm", p)
            self._add_accumulator("beta1_pow_acc", p, fill_value=self._beta1, shape=[1])

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        return block.append_op(
            "adamax",
            inputs={
                "Param": [param],
                "Grad": [grad],
                "Moment": [self._get_accumulator("moment", param)],
                "InfNorm": [self._get_accumulator("inf_norm", param)],
                "Beta1Pow": [self._get_accumulator("beta1_pow_acc", param)],
                "LearningRate": [self._create_param_lr(param_and_grad)],
            },
            outputs={
                "ParamOut": [param],
                "MomentOut": [self._get_accumulator("moment", param)],
                "InfNormOut": [self._get_accumulator("inf_norm", param)],
            },
            attrs={"beta1": self._beta1, "beta2": self._beta2, "epsilon": self._epsilon},
        )

    def _finish_update(self, block, parameters_and_grads):
        # advance beta1^t per param
        for param, grad in parameters_and_grads:
            if grad is None:
                continue
            b1p = self._get_accumulator("beta1_pow_acc", param)
            block.append_op(
                "scale",
                inputs={"X": [b1p]},
                outputs={"Out": [b1p]},
                attrs={"scale": self._beta1},
            )


class DecayedAdagradOptimizer(Optimizer):
    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self.type = "decayed_adagrad"
        self._decay, self._epsilon = decay, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        moment = self._get_accumulator("moment", param)
        return block.append_op(
            "decayed_adagrad",
            inputs={
                "Param": [param],
                "Grad": [grad],
                "Moment": [moment],
                "LearningRate": [self._create_param_lr(param_and_grad)],
            },
            outputs={"ParamOut": [param], "MomentOut": [moment]},
            attrs={"decay": self._decay, "epsilon": self._epsilon},
        )


class AdadeltaOptimizer(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, rho=0.95, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self.type = "adadelta"
        self._epsilon, self._rho = epsilon, rho

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("_avg_squared_grad", p)
            self._add_accumulator("_avg_squared_update", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        asg = self._get_accumulator("_avg_squared_grad", param)
        asu = self._get_accumulator("_avg_squared_update", param)
        return block.append_op(
            "adadelta",
            inputs={
                "Param": [param],
                "Grad": [grad],
                "AvgSquaredGrad": [asg],
                "AvgSquaredUpdate": [asu],
            },
            outputs={
                "ParamOut": [param],
                "AvgSquaredGradOut": [asg],
                "AvgSquaredUpdateOut": [asu],
            },
            attrs={"epsilon": self._epsilon, "rho": self._rho},
        )


class RMSPropOptimizer(Optimizer):
    def __init__(
        self,
        learning_rate,
        rho=0.95,
        epsilon=1e-6,
        momentum=0.0,
        centered=False,
        **kwargs
    ):
        super().__init__(learning_rate, **kwargs)
        self.type = "rmsprop"
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("momentum", p)
            self._add_accumulator("mean_square", p)
            self._add_accumulator("mean_grad", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        return block.append_op(
            "rmsprop",
            inputs={
                "Param": [param],
                "Grad": [grad],
                "Moment": [self._get_accumulator("momentum", param)],
                "MeanSquare": [self._get_accumulator("mean_square", param)],
                "MeanGrad": [self._get_accumulator("mean_grad", param)],
                "LearningRate": [self._create_param_lr(param_and_grad)],
            },
            outputs={
                "ParamOut": [param],
                "MomentOut": [self._get_accumulator("momentum", param)],
                "MeanSquareOut": [self._get_accumulator("mean_square", param)],
                "MeanGradOut": [self._get_accumulator("mean_grad", param)],
            },
            attrs={
                "decay": self._rho,
                "epsilon": self._epsilon,
                "momentum": self._momentum,
                "centered": self._centered,
            },
        )


class FtrlOptimizer(Optimizer):
    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self.type = "ftrl"
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("squared", p)
            self._add_accumulator("linear", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        return block.append_op(
            "ftrl",
            inputs={
                "Param": [param],
                "Grad": [grad],
                "SquaredAccumulator": [self._get_accumulator("squared", param)],
                "LinearAccumulator": [self._get_accumulator("linear", param)],
                "LearningRate": [self._create_param_lr(param_and_grad)],
            },
            outputs={
                "ParamOut": [param],
                "SquaredAccumOut": [self._get_accumulator("squared", param)],
                "LinearAccumOut": [self._get_accumulator("linear", param)],
            },
            attrs={"l1": self._l1, "l2": self._l2, "lr_power": self._lr_power},
        )


SGD = SGDOptimizer
Momentum = MomentumOptimizer
LarsMomentum = LarsMomentumOptimizer
Adagrad = AdagradOptimizer
Adam = AdamOptimizer
Adamax = AdamaxOptimizer
DecayedAdagrad = DecayedAdagradOptimizer
Adadelta = AdadeltaOptimizer
RMSProp = RMSPropOptimizer
Ftrl = FtrlOptimizer


class ModelAverage(Optimizer):
    """Parameter averaging for evaluation (optimizer.py:1365 ModelAverage).

    Accumulates a running sum of every trainable parameter after each
    step (one fused `model_average_accum` op per param — the TPU
    re-expression of the reference's sum_1/2/3 rotation: the window
    restarts once num_updates exceeds max_average_window); `apply()`
    swaps params for their windowed average, `restore()` puts the
    trained values back.

        opt.minimize(loss)
        model_average = fluid.optimizer.ModelAverage(0.15)
        ...train...
        with model_average.apply(exe):
            ...evaluate with averaged weights...
    """

    def __init__(
        self,
        average_window_rate,
        min_average_window=10000,
        max_average_window=10000,
        regularization=None,
        name=None,
    ):
        self.average_window = average_window_rate
        self.min_average_window = min_average_window
        self.max_average_window = max_average_window
        self._accums = {}  # param name -> (sum var, num var)
        main = framework.default_main_program()
        block = main.global_block()
        with main._op_role_guard("optimize"):
            for param in block.all_parameters():
                if not param.trainable:
                    continue
                helper = LayerHelper("model_average")
                psum = helper.create_global_variable(
                    name=unique_name.generate(param.name + "_avg_sum"),
                    persistable=True,
                    dtype=param.dtype,
                    shape=param.shape,
                )
                num = helper.create_global_variable(
                    name=unique_name.generate(param.name + "_avg_num"),
                    persistable=True,
                    dtype="float32",
                    shape=[1],
                )
                from .initializer import Constant

                num_upd = helper.create_global_variable(
                    name=unique_name.generate(param.name + "_avg_nupd"),
                    persistable=True,
                    dtype="float32",
                    shape=[1],
                )
                helper.set_variable_initializer(psum, Constant(0.0))
                helper.set_variable_initializer(num, Constant(0.0))
                helper.set_variable_initializer(num_upd, Constant(0.0))
                block.append_op(
                    "model_average_accum",
                    inputs={
                        "Param": [param],
                        "Sum": [psum],
                        "Num": [num],
                        "NumUpdates": [num_upd],
                    },
                    outputs={
                        "SumOut": [psum],
                        "NumOut": [num],
                        "NumUpdatesOut": [num_upd],
                    },
                    attrs={
                        "average_window_rate": float(average_window_rate),
                        "min_average_window": int(min_average_window),
                        "max_average_window": int(max_average_window),
                    },
                )
                self._accums[param.name] = (psum, num)

    def apply(self, executor, need_restore=True):
        """Context manager: params := sum/num inside, restored after."""
        import contextlib

        from .core.scope import global_scope

        outer = self

        @contextlib.contextmanager
        def ctx():
            scope = global_scope()
            backup = {}
            for pname, (psum, num) in outer._accums.items():
                backup[pname] = np.array(scope.get(pname))
                s = np.asarray(scope.get(psum.name))
                n = float(np.asarray(scope.get(num.name)).reshape(-1)[0])
                if n > 0:
                    scope.set(pname, (s / n).astype(backup[pname].dtype))
            try:
                yield
            finally:
                if need_restore:
                    for pname, val in backup.items():
                        scope.set(pname, val)

        return ctx()

    def restore(self, executor):
        """No-op when apply() restored on exit (reference API parity)."""




class GradientMergeOptimizer:
    """Gradient accumulation over k micro-batches (the capability of the
    reference's ir/multi_batch_merge_pass, re-designed compile-first).

    Where the reference rewrites the graph into N forward/backward copies
    per step, here `minimize` splits training into TWO compiled programs
    with static shapes and no data-dependent control flow:

      * the MAIN program accumulates grads into persistable buffers
        (`<param>@GRAD@MERGED`) each `exe.run(main)` — no weight update;
      * `apply_program` applies the inner optimizer on the averaged
        buffers and zeroes them — run it every k-th micro-batch.

        opt = fluid.optimizer.GradientMergeOptimizer(
            fluid.optimizer.Adam(1e-3), k_steps=4)
        apply_prog = opt.minimize(loss)
        exe.run(fluid.default_startup_program())
        for i, batch in enumerate(batches):
            exe.run(feed=batch, fetch_list=[loss])
            if (i + 1) % 4 == 0:
                exe.run(apply_prog)

    Gradient clip / regularization configured on the inner optimizer
    apply at merge time (on the averaged grad), matching the reference's
    once-per-merged-batch semantics.
    """

    def __init__(self, inner_optimizer, k_steps=1, avg=True):
        if k_steps < 1:
            raise ValueError("k_steps must be >= 1")
        self.inner = inner_optimizer
        self.k_steps = int(k_steps)
        self.avg = bool(avg)
        self.apply_program = None

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        with phase("build.minimize"):
            return self._minimize(loss, startup_program, parameter_list,
                                  no_grad_set)

    def _minimize(self, loss, startup_program, parameter_list, no_grad_set):
        from .initializer import Constant
        from .layers import nn as _nn

        main = framework.default_main_program()
        startup = startup_program or framework.default_startup_program()
        block = main.global_block()
        params_grads = self.inner.backward(
            loss, startup, parameter_list, no_grad_set)

        merged = []  # (param, acc var)
        with main._op_role_guard("optimize"):
            for param, grad in params_grads:
                if grad is None or not param.trainable:
                    continue
                acc = block.create_var(
                    name=param.name + "@GRAD@MERGED",
                    shape=list(param.shape),
                    dtype=param.dtype,
                    persistable=True,
                    stop_gradient=True,
                )
                sb = startup.global_block()
                sv = sb.create_var(name=acc.name, shape=list(param.shape),
                                   dtype=param.dtype, persistable=True)
                Constant(0.0)(sv, sb)
                # acc += grad, in place on the persistable name
                block.append_op(
                    "elementwise_add",
                    inputs={"X": [acc.name], "Y": [grad.name]},
                    outputs={"Out": [acc.name]},
                    attrs={"axis": -1},
                )
                merged.append((param, acc))

        # the apply program: shares the scope by NAME with main
        apply_prog = framework.Program()
        with framework.program_guard(apply_prog, startup):
            ablock = apply_prog.global_block()
            pg = []
            for param, acc in merged:
                p2 = framework.Parameter(
                    ablock, list(param.shape), param.dtype, name=param.name)
                p2.trainable = True
                p2.optimize_attr = param.optimize_attr
                # per-param decay/clip must survive into merge-time
                # apply_gradients (regularizer.py / clip.py read these)
                p2.regularizer = param.regularizer
                p2.gradient_clip_attr = param.gradient_clip_attr
                ablock.vars[param.name] = p2
                a2 = ablock.create_var(
                    name=acc.name, shape=list(param.shape), dtype=param.dtype,
                    persistable=True, stop_gradient=True)
                g = (
                    _nn.scale(a2, scale=1.0 / self.k_steps)
                    if self.avg and self.k_steps > 1 else a2
                )
                pg.append((p2, g))
            self.inner.apply_gradients(pg)
            # zero the buffers for the next merge window
            with apply_prog._op_role_guard("optimize"):
                for param, acc in merged:
                    ablock.append_op(
                        "fill_constant",
                        inputs={},
                        outputs={"Out": [acc.name]},
                        attrs={"shape": list(acc.shape),
                               "dtype": param.dtype, "value": 0.0},
                    )
        self.apply_program = apply_prog
        return apply_prog
