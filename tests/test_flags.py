"""Every declared flag has a reader.

``set_flags`` accepts any key, so a declaration in ``flags._DEFAULTS``
buys a caller nothing; it is only worth its line when the package reads
it.  A flag nothing reads is a knob the next reader must rule out."""
import os
import re

from paddle_tpu.utils import flags

PKG = os.path.dirname(os.path.dirname(os.path.abspath(flags.__file__)))


def _package_sources_without_the_table():
    """All of paddle_tpu/*.py as one string, flags.py's ``_DEFAULTS``
    literal cut out (the declarations themselves are not reads)."""
    chunks = []
    for root, _, files in os.walk(PKG):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(root, f)
            with open(path) as fh:
                src = fh.read()
            if os.path.samefile(path, flags.__file__):
                start = src.index("_DEFAULTS: Dict")
                src = src[:start] + src[src.index("\n}\n", start):]
            chunks.append(src)
    return "\n".join(chunks)


def test_every_declared_flag_is_read_in_the_package():
    src = _package_sources_without_the_table()
    unread = [
        k for k in flags._DEFAULTS
        # flag("name") / get_flags("name") / "FLAGS_name": a quoted name
        if not re.search(r"""["'](?:FLAGS_)?%s["']"""
                         % re.escape(k[len("FLAGS_"):]), src)]
    assert unread == [], (
        f"declared in flags._DEFAULTS, read nowhere under paddle_tpu/: "
        f"{unread}")
