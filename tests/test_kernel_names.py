"""Every Pallas kernel is named from the one table in
``repro/kernels/names.py``, so a compiled step and a profiler trace name each
kernel call after its public function."""

import ast
import glob
import os

import pytest

from repro.kernels import names

KERNELS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src", "repro", "kernels")


def _pallas_calls(path):
    tree = ast.parse(open(path).read())
    return [node for node in ast.walk(tree) if isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "pallas_call"]


MODULES = sorted(p for p in glob.glob(os.path.join(KERNELS, "*.py"))
                 if _pallas_calls(p))


@pytest.mark.parametrize("path", MODULES, ids=os.path.basename)
def test_every_pallas_call_is_named_from_the_table(path):
    for call in _pallas_calls(path):
        kw = {k.arg: k.value for k in call.keywords}
        assert "name" in kw, f"{path}:{call.lineno} passes no name="
        value = kw["name"]
        assert (isinstance(value, ast.Attribute)
                and isinstance(value.value, ast.Name)
                and value.value.id == "names"), \
            f"{path}:{call.lineno}: name= is not taken from kernels/names.py"
        assert getattr(names, value.attr) in names.KERNEL_NAMES


def test_the_table_names_nine_kernels_once_each():
    called = [k.value.attr for p in MODULES for c in _pallas_calls(p)
              for k in c.keywords if k.arg == "name"]
    assert len(called) == len(set(called)) == len(names.KERNEL_NAMES) == 9
    assert {getattr(names, a) for a in called} == set(names.KERNEL_NAMES)
    from repro import kernels
    # each name is the kernel's public function
    for n in names.KERNEL_NAMES:
        assert callable(getattr(kernels, n)), n
