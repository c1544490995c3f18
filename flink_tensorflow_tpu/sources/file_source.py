"""FileSplitSource — split-based record-file source over io/files.py.

The split-based successor of ``RecordFileSource``: instead of a frozen
stride (subtask i decodes records ``i, i+N, ...`` of the concatenation),
each FILE — or, with ``records_per_split``, each record RANGE within a
file — is one :class:`FileSplit` that any reader can pull.  Skewed file
sizes stop mattering: the reader stuck on the big file keeps reading it
while its peers drain the small ones (work stealing).

Replay skips cheaply: frames are length-prefixed, so seeking to
``start + offset`` walks headers without decoding payloads (the same
trick RecordFileSource uses for strides).
"""

from __future__ import annotations

import dataclasses
import typing

from flink_tensorflow_tpu.io.files import iter_record_frames
from flink_tensorflow_tpu.sources.api import (
    ListSplitEnumerator,
    SourceReader,
    SourceSplit,
    SplitEnumerator,
    SplitSource,
)
from flink_tensorflow_tpu.tensors.serde import decode_record


@dataclasses.dataclass
class FileSplit(SourceSplit):
    """A record range of one frame file: ``[start, stop)`` record
    indices within the file (``stop=None`` = through end of file)."""

    path: str = ""
    start: int = 0
    stop: typing.Optional[int] = None


class _FileSplitReader(SourceReader):
    def read(self, split: FileSplit) -> typing.Iterator[typing.Any]:
        first = split.start + split.offset
        for i, payload in enumerate(iter_record_frames(split.path)):
            if split.stop is not None and i >= split.stop:
                return
            if i >= first:
                yield decode_record(payload)


class FileSplitSource(SplitSource):
    """Bounded split source over one or more frame files.

    ``records_per_split=None`` (default): one split per file.  With a
    value, each file is chunked into ranges of at most that many records
    (the chunking scan walks frame headers only — no payload decode) so
    a single huge file still parallelizes.
    """

    #: THE write-ahead-log ingest path the exactly-once boundary lint
    #: prescribes: durable frame files, split offsets in snapshots.
    wal_fronted = True

    def __init__(self, paths: typing.Union[str, typing.Sequence[str]], *,
                 records_per_split: typing.Optional[int] = None,
                 schema=None):
        if records_per_split is not None and records_per_split <= 0:
            raise ValueError(
                f"records_per_split must be positive, got {records_per_split}")
        self.paths = [paths] if isinstance(paths, str) else list(paths)
        self.records_per_split = records_per_split
        self.schema = schema

    def create_enumerator(self) -> SplitEnumerator:
        splits: typing.List[FileSplit] = []
        if self.records_per_split is None:
            for path in self.paths:
                splits.append(FileSplit(split_id=path, path=path))
        else:
            per = self.records_per_split
            for path in self.paths:
                count = sum(1 for _ in iter_record_frames(path))
                for start in range(0, count, per):
                    stop = min(start + per, count)
                    splits.append(FileSplit(
                        split_id=f"{path}[{start}:{stop}]",
                        path=path, start=start, stop=stop,
                    ))
        return ListSplitEnumerator(splits)

    def create_reader(self, ctx) -> SourceReader:
        return _FileSplitReader()

    def plan_split_count(self) -> typing.Optional[int]:
        # Chunked counts need a file scan — not a plan-time cost; the
        # per-file mode is exact for free.
        return len(self.paths) if self.records_per_split is None else None
