"""Mean exit step of a looped LM over the first steps of the run: sum_t t *
q_t, q_t the mean over tokens and steps of the exit distribution, from the
persistable [total_ut_steps + 1] `var` in which the training program sums
mean_n q_t over its first 64 steps (the last element counts them;
paddle_tpu/models/ouro.py writes it as moe_ffn writes TokensPerExpert).
1 .. total_ut_steps; 1.875 under a zero gate at four steps (q = 1/2, 1/4,
1/8, 1/8).  A health counter, read the same whenever a run looks: it says
over how many loop steps the loss was spread while the window was timed,
which `train_mfu` takes on trust when it credits every step's operations.
`last`, where the program has it, is the [total_ut_steps] distribution of
the last step run, which is logged beside it: on a memorised ring the gate
collapses onto step 1 after ~90 steps, and near 1 means the later passes
are computed and carry no loss.

None where the program has no such variable (another architecture, or a
program from before the statistic) or no step has run."""


def read(ctx, var, last=None):
    import numpy as np

    main, scope = ctx.get("main"), ctx.get("scope")
    if main is None or scope is None:
        return None
    if not main.global_block().has_var(var):
        return None
    early = scope.find_var(var)
    if early is None:
        return None
    early = np.asarray(early, "float64").reshape(-1)
    q, steps = early[:-1], early[-1]
    if not steps:
        return None
    q = q / steps
    ctx["log"]("exit_stat: mean exit distribution over the first %d steps %s"
               % (steps, [round(float(x), 4) for x in q]))
    at_last = scope.find_var(last) if last else None
    if at_last is not None:
        ctx["log"]("exit_stat: of the last step run %s" % [
            round(float(x), 4) for x in np.asarray(at_last).reshape(-1)])
    return float((np.arange(1, q.size + 1) * q).sum())
