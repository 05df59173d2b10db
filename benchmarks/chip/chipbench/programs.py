"""The compiled programs a traced window runs, read back after it.

In a traced run each driver notes the jitted programs it calls during
set-up, with the abstract arguments of every shape they are called with
there (set-up runs every shape the window runs), and the window calls the
programs as an untraced run does. After the window :meth:`Noted.texts`
lowers and compiles each again for those arguments, which the persistent
compile cache serves, and returns the compiled HLO text: its ``op_name``
metadata names the scopes (``span_reduce.program_scopes``) that the trace's
operations carry none of. An untraced run notes nothing.
"""

from __future__ import annotations


class Noted:
    """Jitted programs, each with the abstract arguments of every shape it
    was noted with."""

    def __init__(self):
        self.calls: dict = {}  # (label, shapes) -> (jitted, abstract arguments)

    def note(self, label: str, jitted, args: tuple) -> None:
        import jax

        leaves = jax.tree_util.tree_leaves(args)
        key = (label, tuple((x.shape, str(x.dtype)) for x in leaves))
        if key not in self.calls:
            self.calls[key] = (jitted, jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), args))

    def noting(self, label: str, jitted):
        """``jitted``, noting the arguments of each call."""
        def call(*args):
            self.note(label, jitted, args)
            return jitted(*args)
        return call

    def texts(self) -> list:
        """[(label, compiled HLO text)] of every shape noted."""
        return [(label, jitted.lower(*args).compile().as_text())
                for (label, _), (jitted, args) in self.calls.items()]
