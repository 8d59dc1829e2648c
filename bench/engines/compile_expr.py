"""The compiled-expression engine, as ``python -m repro.launch.serve
--sam`` builds it: ``compile_expr(expr, Format(formats),
Schedule(order, locate), dims)``, a ``CompiledExpr`` that ``SamServer``
serves in three stages (``encode_batch``, ``execute_encoded``,
``decode_batch``). A request carries every operand as its dense array,
and the answer is the served ``FiberTree``'s dense form."""
from benchlib.load import program


def _format(config):
    return program().Format(dict(config["formats"]))


def build(config):
    p = program()
    schedule = p.Schedule(loop_order=tuple(config["order"]),
                          locate=frozenset(tuple(x) for x in
                                           config.get("locate", [])))
    return p.compile_expr(config["expr"], _format(config), schedule,
                          dict(config["dims"]))


def arrays(ops):
    return {name: op.to_dense() for name, op in ops.items()}


def request(config, arrays):
    return program().Request(config["expr"], arrays,
                             formats=_format(config),
                             dims=dict(config["dims"]))


def answer(result):
    return result.to_dense()
