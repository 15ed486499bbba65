"""A reader that is another cell's reader under this cell's name: the
`compute` of the file beside this one, whatever characters its name has
(`kernel_ms.scan.py` is no module name an `import` can spell)."""
import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def compute_of(name: str):
    spec = importlib.util.spec_from_file_location(
        "chipbench_layers_" + name.replace(".", "_").replace("-", "_"),
        os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.compute
