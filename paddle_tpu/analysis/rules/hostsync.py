"""``host-sync-in-jit`` — the ISSUE 6 decode lint, generalized (ISSUE 10
tentpole part c).

Two protected surfaces:

* **traced function bodies** — any function/lambda handed to
  ``ledgered_jit`` / ``pjit`` (or decorated with them): a host sync inside
  a traced body either fails at trace time in the best case or, worse,
  silently constant-folds a device round-trip into every dispatch.
* **the decode dispatch critical section** — the engine functions the
  double-buffered pipeline keeps host-sync-free so readback hides under
  device compute. The allowlist marker on the designated readback lines is
  ``serve-readback-ok`` (legacy) / ``lint: host-sync-in-jit-ok``.

The forbidden direction is device->host: ``np.asarray`` on device values,
``block_until_ready``, ``device_get``. ``jnp.asarray`` (host->device
upload) never blocks on the device and stays legal.
"""
import ast
import re

from ..engine import Finding, rule

#: engine functions forming the decode dispatch critical section
DECODE_CRITICAL = {
    "paddle_tpu/inference/continuous.py": {
        "step", "_dispatch_decode", "_read_back_in_lock",
        "_process_block", "drain",
        # disaggregation (ISSUE 16): adopting a handed-off request inserts
        # pages on the decode replica's dispatch path — it must stay as
        # host-sync-free as any other admission (jnp.asarray uploads only;
        # the key_base rebuild is the one designated readback)
        "adopt_request",
        # ragged plane (ISSUE 20): the mixed prefill+decode dispatch IS the
        # decode critical section now — same contract, same designated
        # readbacks (the sync-path host copy and nothing else)
        "_step_ragged", "_dispatch_ragged", "_dispatch_ragged_mixed",
    },
}

#: the traced-shim factories whose callable argument becomes device code
_TRACE_WRAPPERS = {"ledgered_jit", "pjit"}

# (?<!j) spares jnp.asarray; the regex runs per source line for exact
# parity with the original lint (attribute spellings like xs.block_until_
# ready() have no single AST shape)
_SYNC = re.compile(r"(?<!j)np\.asarray\(|block_until_ready|device_get")


def _scan_span(fi, lo, hi, where, findings):
    for ln in range(lo, hi + 1):
        text = fi.line(ln)
        if _SYNC.search(text):
            findings.append(Finding(
                fi.path, ln, "host-sync-in-jit",
                f"blocking host sync inside {where} — move it to a "
                f"designated readback point (or tag a deliberate "
                f"readback with  # lint: host-sync-in-jit-ok)"))


def _traced_callables(fi):
    """(node, description) for every function body that gets traced."""
    out = []
    for node in ast.walk(fi.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                name = dec.func if isinstance(dec, ast.Call) else dec
                tail = (name.attr if isinstance(name, ast.Attribute)
                        else name.id if isinstance(name, ast.Name)
                        else None)
                if tail in _TRACE_WRAPPERS:
                    out.append((node, f"traced function {node.name!r}"))
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        tail = (f.attr if isinstance(f, ast.Attribute)
                else f.id if isinstance(f, ast.Name) else None)
        if tail not in _TRACE_WRAPPERS or not node.args:
            continue
        # the traced callable may sit behind vmap/shard_map wrappers:
        # collect every lambda and same-module def referenced in arg0
        for sub in ast.walk(node.args[0]):
            if isinstance(sub, ast.Lambda):
                out.append((sub, "a traced lambda"))
            elif isinstance(sub, ast.Name) and sub.id in fi.functions:
                out.append((fi.functions[sub.id],
                            f"traced function {sub.id!r}"))
    return out


@rule("host-sync-in-jit",
      markers=("serve-readback-ok",),
      description="no device->host sync inside traced functions or the "
                  "decode dispatch critical section")
def host_sync_in_jit(index):
    findings = []
    seen = set()
    for fi in index.iter_files("paddle_tpu/"):
        spans = []
        for node, where in _traced_callables(fi):
            spans.append((node.lineno, node.end_lineno, where))
        for fname in DECODE_CRITICAL.get(fi.path, ()):
            fn = None
            for q, n in fi.functions.items():
                if q == fname or q.endswith(f".{fname}"):
                    fn = n
                    break
            if fn is not None:
                spans.append((fn.lineno, fn.end_lineno,
                              "the decode dispatch critical section"))
        for lo, hi, where in spans:
            key = (fi.path, lo, hi)
            if key in seen:
                continue
            seen.add(key)
            _scan_span(fi, lo, hi, where, findings)
    # the same line can fall in overlapping spans (a traced def inside a
    # critical section) — report once
    uniq = {}
    for f in findings:
        uniq.setdefault((f.path, f.line), f)
    return list(uniq.values())
