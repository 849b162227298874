"""What the model tests that cut one expert layer into chips' shares hold
in common (tests/test_kanana2_model.py, test_trinity_model.py,
test_kimi_linear_model.py, test_qwen3_next_model.py and
test_moe_ffn_op.py's Nemotron-H case): a model's own `_experts(h, hp,
is_test)` built as a Program of its own that holds experts [offset, offset +
held), given weights and run through the Executor.
"""

import jax.numpy as jnp
import numpy as np

import paddle_tpu as fluid
from paddle_tpu import framework, layers, unique_name


def share_through_the_executor(experts, config, w, offset, held,
                               shared_bases=None):
    """`experts` is the builder's `_experts`, `config` the class its share
    subclasses; `w` holds "x", "router", "gate_up" (the up weight alone for
    ungated experts), "down", the shared branch's weights in the order the
    builder creates them under "shared" (their base names `shared_bases`
    where the test states them) and, where the router selects with one,
    "bias".  -> (routed + shared, routed alone, counts)."""
    hp = type("Share", (config,), {"num_local_experts": held,
                                   "expert_offset": offset})
    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = layers.data("x", shape=list(w["x"].shape),
                        append_batch_size=False)
        y = experts(x, hp, is_test=False)
    block = main.global_block()
    (moe,) = [op for op in block.ops if op.type == "moe_ffn"]
    init = {moe.inputs["RouterW"][0]: w["router"],
            moe.inputs["GateUpW"][0]: w["gate_up"][offset:offset + held],
            moe.inputs["DownW"][0]: w["down"][offset:offset + held]}
    if "bias" in w:
        init[moe.inputs["ExpertBias"][0]] = w["bias"]
    shared = [p.name for p in block.all_parameters()
              if p.name.startswith("shared_")]
    if shared_bases is not None:
        assert [n.rsplit("_", 1)[0] for n in shared] == shared_bases
    init.update(zip(shared, w["shared"]))
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for name, value in init.items():
            assert tuple(np.asarray(scope.find_var(name)).shape) == (
                value.shape), name
            scope.set(name, jnp.asarray(value))
        return exe.run(main, feed={"x": w["x"]}, fetch_list=[
            y, moe.outputs["Y"][0], moe.outputs["TokensPerExpert"][0]])
