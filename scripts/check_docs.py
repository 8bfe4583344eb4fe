"""Docs drift gate: every command the docs show must actually parse.

Scans README.md and docs/*.md for

* fenced ``bash``/``sh``/``shell`` blocks — each command line is checked:
  referenced script/example files must exist, ``python -m repro.*``
  modules must resolve to a source file, and every ``--flag`` the docs
  pass must appear as an ``add_argument`` in that module's source (the
  static check that catches renamed/removed launcher flags);
* ``python -m repro.launch.*`` modules are additionally *run* with
  ``--help`` (unless ``--static``) — the "does it parse" proof;
* relative markdown links — the target file must exist (dead-link
  detection; http(s)/mailto/anchors are ignored).

Exit code 1 with a consolidated report when anything drifted.  Wired
into ``scripts/check.sh --fast`` and CI.

    python scripts/check_docs.py [--static] [--verbose]
"""
from __future__ import annotations

import argparse
import os
import re
import shlex
import subprocess
import sys

DOC_GLOBS = ["README.md", "docs"]
SHELL_INFO = {"bash", "sh", "shell", "console", ""}
FENCE_RE = re.compile(r"^```(\w*)\s*$")
LINK_RE = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
ARG_RE = re.compile(r"""add_argument\(\s*['"](--[A-Za-z0-9-]+)['"]""")
#: launchers share flag builders in repro/launch/args.py — a module
#: importing it accepts those flags too (the static check follows the
#: import; the live --help run proves it for real)
SHARED_ARGS_RE = re.compile(r"repro\.launch(?:\.args|\s+import\s+args)")


def doc_files(root: str) -> list[str]:
    out = []
    for entry in DOC_GLOBS:
        path = os.path.join(root, entry)
        if os.path.isdir(path):
            out += sorted(os.path.join(path, f) for f in os.listdir(path)
                          if f.endswith(".md"))
        elif os.path.exists(path):
            out.append(path)
    return out


def shell_commands(text: str):
    """Yield (lineno, command) from fenced shell blocks, with ``\\``
    continuations joined and comments stripped."""
    in_block, shell = False, False
    pending, pending_ln = "", 0
    for ln, line in enumerate(text.splitlines(), 1):
        m = FENCE_RE.match(line.strip())
        if m:
            if in_block:
                in_block = False
            else:
                in_block, shell = True, m.group(1).lower() in SHELL_INFO
            continue
        if not (in_block and shell):
            continue
        line = line.split("#", 1)[0].rstrip()
        if not line.strip():
            continue
        if pending:
            line = pending + " " + line.strip()
        if line.rstrip().endswith("\\"):
            pending, pending_ln = line.rstrip()[:-1], pending_ln or ln
            continue
        yield (pending_ln or ln), line.strip()
        pending, pending_ln = "", 0


def strip_env(tokens: list[str]) -> list[str]:
    while tokens and re.match(r"^[A-Za-z_][A-Za-z0-9_]*=", tokens[0]):
        tokens = tokens[1:]
    return tokens


def module_source(root: str, module: str) -> str | None:
    path = os.path.join(root, "src", *module.split(".")) + ".py"
    return path if os.path.exists(path) else None


def module_flags(path: str) -> set[str]:
    """Flags a module accepts: its own ``add_argument`` calls, plus the
    shared builders' when it imports ``repro.launch.args``."""
    with open(path) as f:
        src = f.read()
    flags = set(ARG_RE.findall(src))
    if SHARED_ARGS_RE.search(src):
        shared = os.path.join(os.path.dirname(path), "args.py")
        if os.path.exists(shared):
            with open(shared) as f:
                flags |= set(ARG_RE.findall(f.read()))
    return flags


def check_command(root: str, doc: str, ln: int, cmd: str, errors: list,
                  modules_used: set):
    try:
        tokens = strip_env(shlex.split(cmd))
    except ValueError:
        errors.append(f"{doc}:{ln}: unparseable shell line: {cmd!r}")
        return
    if not tokens:
        return
    exe = tokens[0]
    if exe in ("bash", "sh") and len(tokens) > 1:
        target = tokens[1]
        if not os.path.exists(os.path.join(root, target)):
            errors.append(f"{doc}:{ln}: missing script {target!r}")
        return
    if exe.endswith(".sh") or exe.startswith("scripts/"):
        if not os.path.exists(os.path.join(root, exe)):
            errors.append(f"{doc}:{ln}: missing script {exe!r}")
        return
    if exe not in ("python", "python3"):
        return                                   # pip, git, … — not ours
    rest = tokens[1:]
    if rest[:1] == ["-m"]:
        if len(rest) < 2:
            return
        module, args = rest[1], rest[2:]
        if not module.startswith("repro."):
            return                               # pytest etc.
        src = module_source(root, module)
        if src is None:
            errors.append(f"{doc}:{ln}: module {module!r} does not exist")
            return
        modules_used.add(module)
        known = module_flags(src)
        for flag in (t.split("=", 1)[0] for t in args
                     if t.startswith("--")):
            if flag not in known:
                errors.append(f"{doc}:{ln}: {module} has no {flag!r} "
                              f"(doc drift — known: {sorted(known)})")
    elif rest and rest[0].endswith(".py"):
        script = rest[0]
        if not os.path.exists(os.path.join(root, script)):
            errors.append(f"{doc}:{ln}: missing file {script!r}")


def check_links(root: str, doc: str, text: str, errors: list):
    for ln, line in enumerate(text.splitlines(), 1):
        for target in LINK_RE.findall(line):
            if re.match(r"^[a-z]+:", target) or target.startswith("#"):
                continue                         # absolute URL / anchor
            rel = target.split("#", 1)[0]
            if not rel:
                continue
            base = root if rel.startswith("/") else os.path.dirname(doc)
            if not os.path.exists(os.path.join(base, rel.lstrip("/"))):
                errors.append(f"{doc}:{ln}: dead link {target!r}")


def run_help(root: str, module: str, errors: list, verbose: bool):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    try:
        res = subprocess.run([sys.executable, "-m", module, "--help"],
                             capture_output=True, text=True, timeout=180,
                             cwd=root, env=env)
    except subprocess.TimeoutExpired:
        errors.append(f"{module}: --help timed out")
        return
    if res.returncode != 0:
        errors.append(f"{module}: --help exited {res.returncode}:\n"
                      f"{res.stderr.strip()[-500:]}")
    elif verbose:
        print(f"[check-docs] {module} --help ok")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--static", action="store_true",
                    help="skip the live `-m <module> --help` runs")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    errors: list[str] = []
    modules_used: set[str] = set()
    n_cmds = 0
    docs = doc_files(root)
    for doc in docs:
        with open(doc) as f:
            text = f.read()
        rel = os.path.relpath(doc, root)
        for ln, cmd in shell_commands(text):
            n_cmds += 1
            check_command(root, rel, ln, cmd, errors, modules_used)
        check_links(root, doc, text, errors)
    if not args.static:
        for module in sorted(m for m in modules_used
                             if m.startswith("repro.launch.")):
            run_help(root, module, errors, args.verbose)
    if errors:
        print(f"[check-docs] FAILED ({len(errors)} problem(s) across "
              f"{len(docs)} docs):")
        for e in errors:
            print(f"  {e}")
        return 1
    print(f"[check-docs] ok: {n_cmds} commands, {len(docs)} docs, "
          f"{len(modules_used)} modules"
          + ("" if args.static else
         f" ({len([m for m in modules_used if m.startswith('repro.launch.')])}"
             " --help runs)"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
