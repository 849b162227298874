"""Public-API signature dump (tools/print_signatures.py analog).

Prints one line per public symbol — `module.name (args...)` — sorted, so a
diff against a committed snapshot catches accidental API breaks the way
the reference's diff_api.py CI check does (paddle/scripts/paddle_build.sh).

Usage:
    python tools/print_signatures.py > API.spec
    python tools/diff_api.py API.spec        # non-zero exit on breakage
"""

import inspect
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODULES = [
    "paddle_tpu",
    "paddle_tpu.layers",
    "paddle_tpu.layers.control_flow",
    "paddle_tpu.layers.detection",
    "paddle_tpu.layers.sequence",
    "paddle_tpu.layers.io",
    "paddle_tpu.optimizer",
    "paddle_tpu.initializer",
    "paddle_tpu.regularizer",
    "paddle_tpu.clip",
    "paddle_tpu.metrics",
    "paddle_tpu.evaluator",
    "paddle_tpu.average",
    "paddle_tpu.io",
    "paddle_tpu.backward",
    "paddle_tpu.transpiler",
    "paddle_tpu.inference",
    "paddle_tpu.memory",
    "paddle_tpu.device_info",
    "paddle_tpu.parallel.collective",
    "paddle_tpu.parallel.partition_rules",
    "paddle_tpu.parallel.pipeline",
    "paddle_tpu.transpiler.pipeline",
    "paddle_tpu.serving",
    "paddle_tpu.serving.router",
    "paddle_tpu.ops.pallas_kernels",
    "paddle_tpu.ops.kernel_tuning",
    "paddle_tpu.analysis",
    "paddle_tpu.transpiler.autotune",
    "paddle_tpu.models.transformer",
    "paddle_tpu.utils.memory_analysis",
    "paddle_tpu.dataset.mnist",
    "paddle_tpu.dataset.movielens",
    "paddle_tpu.dataset.wmt14",
    "paddle_tpu.reader.decorator",
]


def _public_names(mod):
    names = getattr(mod, "__all__", None)
    if names is None:
        names = [n for n in dir(mod) if not n.startswith("_")]
    return sorted(set(names))


def _sig(obj):
    try:
        return str(inspect.signature(obj))
    except (TypeError, ValueError):
        return ""


def iter_signatures():
    import importlib

    for modname in MODULES:
        mod = importlib.import_module(modname)
        for name in _public_names(mod):
            obj = getattr(mod, name, None)
            if obj is None or inspect.ismodule(obj):
                continue
            # a re-exported third-party symbol (jax's PartitionSpec as
            # partition_rules.P) is not this package's API to pin: its
            # signature moves with the installed library
            if not (getattr(obj, "__module__", None) or "").startswith(
                    "paddle_tpu"):
                continue
            if inspect.isclass(obj):
                yield "%s.%s %s" % (modname, name, _sig(obj.__init__))
                for mname, meth in sorted(vars(obj).items()):
                    if mname.startswith("_") or not callable(meth):
                        continue
                    yield "%s.%s.%s %s" % (modname, name, mname, _sig(meth))
            elif callable(obj):
                yield "%s.%s %s" % (modname, name, _sig(obj))


def main():
    for line in sorted(set(iter_signatures())):
        print(line)


if __name__ == "__main__":
    main()
