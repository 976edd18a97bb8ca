"""Batch-discipline checker (rule: batch-discipline, codes CFC0xx).

codec/batcher.py is the single admission surface for device math: it
coalesces concurrent stripes into device-sized steps, meters occupancy
and admission wait, and applies bounded-queue backpressure. A
blob-plane module that grabs a raw engine handle and dispatches on it
silently opts its stripes out of all of that — each call is its own
device step, invisible to the codec metrics and to backpressure. The regression shape:

  CFC001  blob-plane import of the raw engine layer (codec.engine /
          get_engine / engine_for) — holding a raw handle is how the
          bypass starts
  CFC002  .encode_parity() / .matrix_apply() dispatched on a receiver
          that is not the admitted facade — blob code must call these
          on an ``admit()``-returned handle (held as ``.codec`` by
          convention) or through BatchCodec.submit_*
  CFC003  raw sub-shard reconstruction (msr_repair_rows /
          msr_reconstruct_rows / msr_helper_rows / msr_verify_rows /
          msr_repair_shard) outside blob/worker.py — the worker is the
          single orchestrator of MSR repair: it owns helper election,
          the pre-writeback verify, the conventional fallback, and the
          repair-traffic metrics; a second call-site forks that
          protocol (helpers serve opaque coefficient rows over
          read_subshard, they never build repair matrices themselves)
  CFC004  ad-hoc XOR-program construction (XorProgFenceChecker, below)
          outside ops/xorprog.py — bitmatrix expansion and schedule
          compilation are fenced there so every leg replays ONE cached,
          CSE'd, digest-stamped schedule; a second expansion site can
          silently disagree with the compiled program

The analysis is syntactic. The admitted receiver convention is a final
attribute/name of ``codec`` (``self.codec``, ``enc.codec``) or an
obvious batcher handle (``batcher``/``admitted``); anything else that
dispatches device math from cubefs_tpu/blob/ is flagged. codemode /
encoder config imports are fine — only the engine layer is fenced.
"""

from __future__ import annotations

import ast

from ..core import Checker, Module, Violation

# names whose import from the codec package hands out raw engine access
_ENGINE_NAMES = {"get_engine", "engine_for", "Engine", "NumpyEngine",
                 "CppEngine", "TpuEngine"}
# receiver final names allowed to dispatch device math in the blob plane
_ADMITTED_RECV = {"codec", "batcher", "admitted"}
_DEVICE_CALLS = {"encode_parity", "matrix_apply"}
# MSR repair-protocol primitives: row construction + one-shot repair.
# Only blob/worker.py may call these (CFC003).
_MSR_CALLS = {"msr_repair_rows", "msr_reconstruct_rows", "msr_helper_rows",
              "msr_verify_rows", "msr_repair_shard"}
_MSR_SANCTIONED = "cubefs_tpu/blob/worker.py"


def _final_name(node: ast.AST) -> str:
    """`self.codec` -> 'codec'; `eng` -> 'eng'."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


class BatchDisciplineChecker(Checker):
    rule = "batch-discipline"
    dirs = ("cubefs_tpu/blob/",)

    def check(self, mod: Module) -> list[Violation]:
        out: list[Violation] = []
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if "codec.engine" in a.name:
                        out.append(self.violation(
                            mod, "CFC001", node,
                            f"import of `{a.name}` from the blob plane — "
                            f"raw engine handles bypass the codec "
                            f"admission surface (codec/batcher.py)"))
            elif isinstance(node, ast.ImportFrom):
                modname = node.module or ""
                if modname.endswith("codec.engine"):
                    out.append(self.violation(
                        mod, "CFC001", node,
                        "import from codec.engine in the blob plane — "
                        "route device math through codec.batcher.admit() "
                        "so stripes coalesce, meter, and backpressure"))
                elif modname.endswith("codec") or ".codec." in modname \
                        or modname == "codec":
                    for a in node.names:
                        if a.name == "engine" or a.name in _ENGINE_NAMES:
                            out.append(self.violation(
                                mod, "CFC001", node,
                                f"import of `{a.name}` from the codec "
                                f"package in the blob plane — raw engine "
                                f"access bypasses the admission surface"))
            elif isinstance(node, ast.Call):
                func = node.func
                called = (func.attr if isinstance(func, ast.Attribute)
                          else func.id if isinstance(func, ast.Name) else "")
                if (called in _MSR_CALLS
                        and mod.relpath != _MSR_SANCTIONED):
                    out.append(self.violation(
                        mod, "CFC003", node,
                        f"`{called}()` outside {_MSR_SANCTIONED} — "
                        f"sub-shard reconstruction is the repair worker's "
                        f"protocol (helper election, pre-writeback verify, "
                        f"conventional fallback, traffic metrics); helpers "
                        f"only apply opaque coefficient rows via "
                        f"read_subshard"))
                if (isinstance(func, ast.Attribute)
                        and func.attr in _DEVICE_CALLS
                        and _final_name(func.value) not in _ADMITTED_RECV):
                    recv = _final_name(func.value) or mod.segment(func.value)
                    out.append(self.violation(
                        mod, "CFC002", node,
                        f".{func.attr}() on raw receiver `{recv}` — blob "
                        f"code must dispatch device math through the "
                        f"admitted facade (codec.batcher.admit(), held "
                        f"as `.codec`) so submissions coalesce into "
                        f"device-sized steps"))
        return out


# names whose call (or import) means "I am expanding GF(256) rows into
# GF(2) bitmatrices / building an XOR schedule by hand"
_XORPROG_NAMES = {"gf_matrix_to_bits", "coeff_bitmatrix", "XorProgram"}
_XORPROG_HOME = "cubefs_tpu/ops/xorprog.py"


class XorProgFenceChecker(Checker):
    """CFC004: XOR-program construction is fenced to ops/xorprog.py.

    The scheduled-XOR path (ops/xorprog.py) owns the bitmatrix
    expansion, the CSE pass, and the slot layout shared with the native
    executor; blob- and codec-plane modules consume compiled programs
    via ``xorprog.apply`` / ``xorprog.program_for`` only. A second
    expansion site (calling ``gf_matrix_to_bits`` on coefficient rows,
    or constructing ``XorProgram`` ad hoc) forks the schedule contract:
    it bypasses the program cache, the schedule digest the chaos drill
    replays, and the bit-identity guarantee the compiled program
    carries. Note rs_kernel.py (ops plane, device bit-matmul) also uses
    gf_matrix_to_bits legitimately — only blob/ and codec/ are fenced.
    """

    rule = "batch-discipline"
    dirs = ("cubefs_tpu/blob/", "cubefs_tpu/codec/")

    def check(self, mod: Module) -> list[Violation]:
        out: list[Violation] = []
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ImportFrom):
                for a in node.names:
                    if a.name in _XORPROG_NAMES:
                        out.append(self.violation(
                            mod, "CFC004", node,
                            f"import of `{a.name}` outside "
                            f"{_XORPROG_HOME} — XOR schedules are "
                            f"compiled there; consume them via "
                            f"xorprog.apply()/program_for()"))
            elif isinstance(node, ast.Call):
                func = node.func
                called = (func.attr if isinstance(func, ast.Attribute)
                          else func.id if isinstance(func, ast.Name) else "")
                if called in _XORPROG_NAMES:
                    out.append(self.violation(
                        mod, "CFC004", node,
                        f"`{called}()` outside {_XORPROG_HOME} — ad-hoc "
                        f"bitmatrix expansion forks the compiled-schedule "
                        f"contract (program cache, schedule digest, "
                        f"bit-identity); call xorprog.apply() or "
                        f"xorprog.program_for() instead"))
        return out
