"""Entry point ``serve_sublayers``: ``serve`` for a model whose layer holds two
attention sublayers (LongCat-Flash).  Such a model publishes ``num_layers``;
``entries/serve.py`` and ``reduce/mla_shapes.py`` read ``num_hidden_layers``,
the count of attention layers (cache rows), which ``sizes`` lacks because it
holds the published keys alone.  This entry adds that one derived count, as
``transformers``' own ``LongcatFlashModel`` does (``config.num_hidden_layers = 2
* config.num_layers``, "to have a correct cache"), and runs ``serve``: the run
it returns is a ``serve`` run (``run.kind``), so every borrowed reader reads."""

from chipbench.entries import serve


def run(ctx):
    ctx.sizes["num_hidden_layers"] = 2 * ctx.sizes["num_layers"]
    return serve.run(ctx)
