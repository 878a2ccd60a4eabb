"""Generate the API + CLI reference pages from docstrings (stdlib-only).

The reference auto-generates these with sphinx autodoc/click plugins
(/root/reference/docs/source/api_reference.rst:1, cli_reference.rst:1); this
image has neither, so the generator is plain ``inspect``: every public module's
docstring, classes (constructor signature, public methods), and functions are
rendered into ``docs/api-reference.md``, and the click CLI tree into
``docs/cli-reference.md``. ``docs/build.py`` runs this before rendering, so the
pages can never go stale against the code.

Usage::

    python docs/gen_api.py            # (re)writes the two pages in docs/
"""

from __future__ import annotations

import importlib
import inspect
import sys
import textwrap
from pathlib import Path

DOCS_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(DOCS_DIR.parent))  # repo root: run from anywhere

#: public modules, in the order they appear on the page
MODULES = [
    "unionml_tpu",
    "unionml_tpu.dataset",
    "unionml_tpu.model",
    "unionml_tpu.type_guards",
    "unionml_tpu.stage",
    "unionml_tpu.data.pipeline",
    "unionml_tpu.train.driver",
    "unionml_tpu.parallel.mesh",
    "unionml_tpu.parallel.sharding",
    "unionml_tpu.parallel.collectives",
    "unionml_tpu.parallel.pipeline",
    "unionml_tpu.models.generate",
    "unionml_tpu.models.structured",
    "unionml_tpu.models.speculative",
    "unionml_tpu.models.layers",
    "unionml_tpu.models.llama",
    "unionml_tpu.models.bert",
    "unionml_tpu.models.vit",
    "unionml_tpu.models.mlp",
    "unionml_tpu.models.moe",
    "unionml_tpu.models.glm4_moe_lite",
    "unionml_tpu.models.bailing_hybrid",
    "unionml_tpu.ops.delta_rule",
    "unionml_tpu.ops.attention",
    "unionml_tpu.ops.ring_attention",
    "unionml_tpu.ops.quant",
    "unionml_tpu.serving.aot",
    "unionml_tpu.serving.app",
    "unionml_tpu.serving.batcher",
    "unionml_tpu.serving.cluster",
    "unionml_tpu.serving.compile",
    "unionml_tpu.serving.continuous",
    "unionml_tpu.serving.faults",
    "unionml_tpu.serving.http",
    "unionml_tpu.serving.metrics",
    "unionml_tpu.serving.openai_api",
    "unionml_tpu.serving.overload",
    "unionml_tpu.serving.prefix_cache",
    "unionml_tpu.serving.replicas",
    "unionml_tpu.serving.serverless",
    "unionml_tpu.serving.tenancy",
    "unionml_tpu.workloads.traces",
    "unionml_tpu.workloads.scenarios",
    "unionml_tpu.workloads.replayer",
    "unionml_tpu.workloads.verdicts",
    "unionml_tpu.observability.trace",
    "unionml_tpu.observability.recorder",
    "unionml_tpu.observability.engine_log",
    "unionml_tpu.observability.prometheus",
    "unionml_tpu.observability.timeseries",
    "unionml_tpu.observability.slo",
    "unionml_tpu.observability.health",
    "unionml_tpu.analysis",
    "unionml_tpu.analysis.engine",
    "unionml_tpu.analysis.project",
    "unionml_tpu.analysis.cfg",
    "unionml_tpu.analysis.dataflow",
    "unionml_tpu.artifact",
    "unionml_tpu.distributed",
    "unionml_tpu.remote",
    "unionml_tpu.launcher",
    "unionml_tpu.gke",
    "unionml_tpu.job_runner",
    "unionml_tpu.resolver",
    "unionml_tpu.templating",
    "unionml_tpu.compile_cache",
    "unionml_tpu.defaults",
]


def _first_paragraph(doc: str | None) -> str:
    if not doc:
        return ""
    return inspect.cleandoc(doc).split("\n\n")[0].replace("\n", " ")


def _signature(obj) -> str:
    import re

    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"
    # default values whose repr embeds a memory address (bound methods, object
    # instances) would re-churn the generated page on every rebuild
    return re.sub(r" at 0x[0-9a-f]+", "", sig)


def _public_members(module):
    """(classes, functions) defined in this module, honoring __all__ when set."""
    allowed = getattr(module, "__all__", None)
    classes, functions = [], []
    for name, obj in sorted(vars(module).items()):
        if name.startswith("_"):
            continue
        if allowed is not None and name not in allowed:
            continue
        if getattr(obj, "__module__", None) != module.__name__:
            continue  # re-exports documented at their home module
        if inspect.isclass(obj):
            classes.append((name, obj))
        elif inspect.isfunction(obj):
            functions.append((name, obj))
    return classes, functions


def _render_class(name: str, cls) -> list[str]:
    lines = [f"### `{name}{_signature(cls)}`", ""]
    doc = _first_paragraph(cls.__doc__)
    if doc:
        lines += [doc, ""]
    methods = []
    for mname, member in sorted(vars(cls).items()):
        if mname.startswith("_"):
            continue
        func = member.__func__ if isinstance(member, (classmethod, staticmethod)) else member
        if inspect.isfunction(func):
            methods.append((mname, func))
        elif isinstance(member, property) and member.fget is not None:
            methods.append((mname, member.fget))
    for mname, func in methods:
        summary = _first_paragraph(func.__doc__)
        lines.append(f"- `{mname}{_signature(func)}`" + (f" — {summary}" if summary else ""))
    if methods:
        lines.append("")
    return lines


def generate_api_page() -> str:
    lines = [
        "# API reference",
        "",
        "Generated from docstrings by `docs/gen_api.py` (the stdlib analog of the",
        "reference's sphinx autodoc page, docs/source/api_reference.rst). Regenerate",
        "with `python docs/gen_api.py`; `docs/build.py` does so automatically.",
        "",
    ]
    for module_name in MODULES:
        module = importlib.import_module(module_name)
        lines += [f"## `{module_name}`", ""]
        summary = _first_paragraph(module.__doc__)
        if summary:
            lines += [summary, ""]
        classes, functions = _public_members(module)
        for name, cls in classes:
            lines += _render_class(name, cls)
        for name, func in functions:
            lines += [f"### `{name}{_signature(func)}`", ""]
            doc = _first_paragraph(func.__doc__)
            if doc:
                lines += [doc, ""]
    import re

    # addresses can also arrive through docstrings (flax injects attribute docs
    # containing default-object reprs); scrub the whole page so rebuilds are
    # byte-stable
    return re.sub(r" at 0x[0-9a-f]+", "", "\n".join(lines).rstrip() + "\n")


def generate_cli_page() -> str:
    import click

    from unionml_tpu.cli import app as cli_app

    lines = [
        "# CLI reference",
        "",
        "Generated from the click command tree by `docs/gen_api.py` (analog of the",
        "reference's docs/source/cli_reference.rst). Entry point: `unionml-tpu`",
        "(also `python -m unionml_tpu.cli`).",
        "",
    ]
    ctx = click.Context(cli_app, info_name="unionml-tpu")
    for name in sorted(cli_app.list_commands(ctx)):
        command = cli_app.get_command(ctx, name)
        lines += [f"## `unionml-tpu {name}`", ""]
        help_text = (command.help or "").strip()
        if help_text:
            lines += [textwrap.dedent(help_text).split("\n\n")[0].replace("\n", " "), ""]
        sub_ctx = click.Context(command, info_name=name)
        usage = command.collect_usage_pieces(sub_ctx)
        lines += ["```", f"unionml-tpu {name} {' '.join(usage)}", "```", ""]
        params = [p for p in command.get_params(sub_ctx) if not getattr(p, "hidden", False)]
        for param in params:
            record = param.get_help_record(sub_ctx)
            if record is None:
                if isinstance(param, click.Argument):
                    lines.append(f"- `{param.name.upper()}` (argument)")
                continue
            opts, desc = record
            lines.append(f"- `{opts}`" + (f" — {desc}" if desc else ""))
        if params:
            lines.append("")
    return "\n".join(lines).rstrip() + "\n"


def main() -> None:
    import os
    import sys

    if os.environ.get("JAX_PLATFORMS"):
        import jax

        jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    pages = {
        DOCS_DIR / "api-reference.md": generate_api_page(),
        DOCS_DIR / "cli-reference.md": generate_cli_page(),
    }
    if "--check" in sys.argv:
        # freshness gate (pre-commit / CI): the committed pages must match what
        # the current docstrings generate — drift fails instead of shipping
        stale = [p.name for p, text in pages.items() if not p.exists() or p.read_text() != text]
        if stale:
            print(f"generated docs out of date: {', '.join(stale)} (run: python docs/gen_api.py)")
            raise SystemExit(1)
        print("generated docs up to date")
        return
    for path, text in pages.items():
        path.write_text(text)
    print(f"wrote {' and '.join(str(p) for p in pages)}")


if __name__ == "__main__":
    main()
