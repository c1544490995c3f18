"""Model abstraction — typed model methods as first-class stream citizens.

Equivalent of the reference's ``Model`` trait whose "methods" are typed
graph signatures (SURVEY.md §2 "`Model` abstraction": ``Model``,
``GraphMethod``).  In the reference a method is a TF ``SignatureDef`` —
named input/output tensor names bound to ``Session.run`` feeds/fetches.
Here a method is a pure function ``(params, inputs) -> outputs`` over
pytrees, plus the input :class:`RecordSchema` the stream coercion layer
validates against.  ``Session.run(feeds, fetches)`` becomes an XLA
executable specialized per batch bucket — compilation is the loader's /
operator-``open()``'s job, mirroring the reference lifecycle (SURVEY.md
§3.3).
"""

from __future__ import annotations

import dataclasses
import typing

from flink_tensorflow_tpu.tensors.schema import RecordSchema

Params = typing.Any  # pytree of jax arrays
ApplyFn = typing.Callable[..., typing.Dict[str, typing.Any]]


@dataclasses.dataclass(frozen=True)
class ModelMethod:
    """One named, typed entry point of a model (a SignatureDef analogue).

    ``fn(params, inputs, **kw)`` takes the batched input pytree (field ->
    ``[B, ...]`` array) and returns a dict of named ``[B, ...]`` outputs.
    ``needs_lengths`` marks methods that take per-record true lengths for
    padded sequence fields (BiLSTM dynamic batching, BASELINE.json:9).
    """

    name: str
    input_schema: RecordSchema
    output_names: typing.Tuple[str, ...]
    fn: ApplyFn
    needs_lengths: bool = False
    #: Preferred on-device compute dtype; bfloat16 keeps the MXU fed.
    compute_dtype: typing.Any = None
    #: Outputs of ``fn`` that are counts made on the device, not answers:
    #: ``[B]`` (one a record; padding rows are left out of the sum) or a
    #: scalar (one a batch).  A runner fetches them with every batch, adds
    #: them to counters of the same name and never puts them in a record.
    count_names: typing.Tuple[str, ...] = ()


class Model:
    """A loaded model: params + named methods.

    Instances are host-side handles; params live wherever the loader put
    them (host at load, HBM after an operator ``open()`` places them).
    """

    def __init__(
        self,
        name: str,
        params: Params,
        methods: typing.Mapping[str, ModelMethod],
        metadata: typing.Optional[dict] = None,
    ):
        self.name = name
        self.params = params
        self._methods = dict(methods)
        self.metadata = dict(metadata or {})

    def method(self, name: str = "serve") -> ModelMethod:
        try:
            return self._methods[name]
        except KeyError:
            raise KeyError(
                f"model {self.name!r} has no method {name!r}; available: {sorted(self._methods)}"
            ) from None

    @property
    def methods(self) -> typing.Mapping[str, ModelMethod]:
        return self._methods

    def with_params(self, params: Params) -> "Model":
        return Model(self.name, params, self._methods, self.metadata)
