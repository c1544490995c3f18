"""What the README and the verify skill send a reader to is there: every
repository path they name in back-ticks exists, and every command of the
README's ``bash`` blocks resolves to a file, a module, a declared console
script or a Makefile.  Nothing is run: a command that starts is the business
of the tests of what it starts.
"""

import importlib
import importlib.util
import pathlib
import re
import shlex
import tomllib

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
DOCS = ("README.md", ".claude/skills/verify/SKILL.md")
#: How a token that claims a file or a directory ends.
PATH_ENDINGS = (".py", ".json", ".md", ".toml", ".cpp", "/")

#: Back-ticked tokens shaped like a path that name nothing committed, and why.
NOT_IN_THE_CHECKOUT = {
    "native/lib/": "built by `make -C native`, git-ignored",
    "chiprun_out/": "written by chiprun, git-ignored",
    "proc-NNNNN/": "a checkpoint shard's directory, one a process, at run time",
    "TABLE.json": "the argument of --cost-table: the reader's own file",
}
#: Scripts that stand for the reader's own in the README's commands: no case.
THE_READERS_OWN = {"my_pipeline.py", "my_cohort_worker.py"}

FENCE = re.compile(r"```(\w*)\n(.*?)```", re.S)


def _path_tokens(text):
    """Words inside back-ticks, outside fenced blocks, that claim a path of the
    repository: they end as a source or data file does, or in a slash, or
    start with a directory of the root or of the package (``a/b`` between two
    options or two mesh axes claims nothing)."""
    found = set()
    for span in re.findall(r"`([^`\n]+)`", FENCE.sub("", text)):
        for word in span.split():
            word = word.strip("(),;:").split("::")[0]
            if not re.fullmatch(r"[\w.\-/]+", word) or word in NOT_IN_THE_CHECKOUT:
                continue  # globs, <placeholders>, URLs, option=value
            head = word.split("/")[0]
            if word.endswith(PATH_ENDINGS) or "/" in word and (
                    (REPO / head).is_dir() or (REPO / "flink_tensorflow_tpu" / head).is_dir()):
                found.add(word)
    return sorted(found)


def _exists(token):
    """At the root or, as the README's Layout table writes them, in the
    package; ``functions/runner.CompiledMethodRunner`` names the module."""
    names = [token]
    if not token.endswith(PATH_ENDINGS):
        names.append(re.sub(r"\.[A-Za-z_]\w*$", "", token) + ".py")
    return any((base / name).exists() for name in names
               for base in (REPO, REPO / "flink_tensorflow_tpu"))


@pytest.mark.parametrize("doc", DOCS)
def test_paths_named_exist(doc):
    tokens = _path_tokens((REPO / doc).read_text())
    assert tokens, f"{doc} names no path: the pattern has stopped matching"
    assert not [t for t in tokens if not _exists(t)]


def _command_heads():
    """The distinct heads of the commands in the README's ``bash`` blocks:
    ``python X.py``, ``python -m a.b``, ``flink-tpu-*``, ``make -C dir``."""
    heads = set()
    for lang, block in FENCE.findall((REPO / "README.md").read_text()):
        if lang != "bash":
            continue
        for line in block.replace("\\\n", " ").splitlines():
            words = shlex.split(line, comments=True)
            while words and re.fullmatch(r"[A-Z_0-9]+=\S*", words[0]):
                words.pop(0)  # FLINK_TPU_TRACE=1 python ...
            if not words:
                continue
            if words[0] in ("python", "python3"):
                if words[1] not in THE_READERS_OWN:
                    heads.add(" ".join(words[:3] if words[1] == "-m" else words[:2]))
            elif words[0] == "make":
                heads.add(" ".join(words[:3]))
            else:
                heads.add(words[0])
    return sorted(heads)


@pytest.mark.parametrize("command", _command_heads())
def test_readme_command_resolves(command, monkeypatch):
    monkeypatch.syspath_prepend(str(REPO))  # as for a reader who starts it at the root
    words = command.split()
    if words[0].startswith("python") and words[1] == "-m":
        spec = importlib.util.find_spec(words[2])
        assert spec is not None, f"no module {words[2]}"
        if spec.submodule_search_locations is not None:  # a package runs its __main__
            assert importlib.util.find_spec(words[2] + ".__main__") is not None
    elif words[0].startswith("python"):
        assert (REPO / words[1]).is_file()
    elif words[0].startswith("flink-tpu-"):
        scripts = tomllib.loads((REPO / "pyproject.toml").read_text())["project"]["scripts"]
        assert words[0] in scripts, f"pyproject.toml declares no script {words[0]}"
        module, _, attr = scripts[words[0]].partition(":")
        assert callable(getattr(importlib.import_module(module), attr))
    elif words[:2] == ["make", "-C"]:
        assert (REPO / words[2] / "Makefile").is_file()
    else:
        pytest.fail(f"README runs {command!r}: say here how such a command resolves")
