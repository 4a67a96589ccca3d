"""Print the optimized HLO of a one-chip training cell's step, without what
only names or locates code, so that the programs of two commits can be
compared.

    python3 bench/tests/step_hlo.py <cell> > step.hlo
    diff parent.hlo change.hlo

Runs on the chip: compiles the cell's jitted step for its state and batch
shapes (``bench/scopes.py:compiled_step``).  Left out of the text:
each instruction's ``metadata={...}`` (``op_name``, which
``jax.named_scope`` writes, and source lines), the source tables, and the
Mosaic kernels' names.  Each kernel instruction is renamed
``%tpu_kernel.<k>`` in order of appearance, and its serialized body is
replaced by the kernel's MLIR text without locations, with the module's
name (the ``pallas_call``'s ``name``) replaced by ``kernel``.
"""
import base64
import json
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness as H  # noqa: E402

_METADATA = re.compile(r', metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
_TABLES = re.compile(
    r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*")
_KERNEL = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = [^\n]*'
                     r'custom_call_target="tpu_custom_call"', re.M)
_BODY = re.compile(r'"body":"((?:[^"\\]|\\.)*)"')


def normalize(txt: str) -> str:
    """``txt`` without metadata, source tables and kernel names."""
    from jax._src.lib.mlir import ir
    txt = _TABLES.sub("\n", _METADATA.sub("", txt))
    for k, name in enumerate(_KERNEL.findall(txt)):
        txt = re.sub(r"%" + re.escape(name) + r"(?![\w.\-])",
                     f"%tpu_kernel.{k}", txt)
    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True

    def body(m):
        raw = base64.b64decode(json.loads(f'"{m.group(1)}"'))
        with ctx:
            asm = ir.Module.parse(raw).operation.get_asm(
                enable_debug_info=False)
        asm = re.sub(r"^module @[\w.\-$]+", "module @kernel", asm)
        return '"body":' + json.dumps(asm)

    return _BODY.sub(body, txt)


def step_hlo(name: str) -> str:
    from bench import scopes
    _, cell, config, traffic = H.find_cell(name)
    devices = H.device_check(cell["chips"])
    H.import_program()
    H.enable_compile_cache()
    return scopes.compiled_step(config, traffic, devices)


if __name__ == "__main__":
    print(normalize(step_hlo(sys.argv[1])), end="")
