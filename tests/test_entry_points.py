"""Every command the README and the Makefile name resolves in this tree.

One case a command: the ```bash blocks of README.md and the recipes of
Makefile. A `python X.py` must name a file of the checkout, a
`python -m a.b` a module that importlib finds, a `python -c` imports that
importlib finds, and every path argument (`pytest tests/x.py`) must exist.
Recipes that run `docker` or `$(MAKE)` are skipped; lines that start no
python are not commands of this repo. Nothing is run.
"""

import ast
import importlib.util
import re
import shlex
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
_ENV_ASSIGNMENT = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*=")


def _readme_commands():
    in_block = False
    for n, line in enumerate((ROOT / "README.md").read_text().splitlines(), 1):
        if line.startswith("```"):
            in_block = line.strip() == "```bash" and not in_block
            continue
        if in_block and line.strip() and not line.lstrip().startswith("#"):
            yield f"README.md:{n}", line


def _makefile_commands():
    text = (ROOT / "Makefile").read_text()
    variables = dict(re.findall(r"^(\w+) \?= (.*)$", text, re.M))
    for n, line in enumerate(text.splitlines(), 1):
        if not line.startswith("\t") or "$(MAKE)" in line or "docker" in line:
            continue
        yield f"Makefile:{n}", re.sub(
            r"\$\((\w+)\)", lambda m: variables.get(m.group(1), m.group(0)), line
        )


def _python_commands():
    for where, line in [*_readme_commands(), *_makefile_commands()]:
        argv = shlex.split(line, comments=True)
        while argv and _ENV_ASSIGNMENT.match(argv[0]):
            argv.pop(0)
        if argv and re.fullmatch(r"python3?", argv[0]):
            yield pytest.param(argv[1:], id=f"{where} {' '.join(argv[:3])}")


@pytest.mark.parametrize("args", _python_commands())
def test_documented_command_resolves(args):
    assert args, "a bare python is not a command"
    if args[0] == "-m":
        assert importlib.util.find_spec(args[1]) is not None, args[1]
        rest = args[2:]
    elif args[0] == "-c":
        imported = [
            alias.name
            for node in ast.walk(ast.parse(args[1]))
            if isinstance(node, ast.Import)
            for alias in node.names
        ]
        assert imported, args[1]
        for name in imported:
            assert importlib.util.find_spec(name) is not None, name
        rest = args[2:]
    else:
        assert args[0].endswith(".py"), args[0]
        assert (ROOT / args[0]).is_file(), args[0]
        rest = args[1:]
    for arg in rest:
        if not arg.startswith("-") and ("/" in arg or arg.endswith(".py")):
            assert (ROOT / arg).exists(), arg
