"""Declarative protobuf messages with deterministic (canonical) marshaling.

Encoding rules match gogo/protobuf proto3 marshaling as used by the
reference for sign-bytes (types/canonical.go, proto/tendermint/types/canonical.proto):
  - fields emitted in ascending field-number order
  - scalar zero values omitted (including sfixed64 zeros — see the golden
    vectors in the reference's types/vote_test.go:88-92)
  - non-nullable embedded messages always emitted; nullable ones omitted
    when None
  - repeated scalar numeric fields packed; repeated messages/bytes unpacked

A class's `fields` list is compiled once, on the first use of that class,
into the source of its decode, encode and `__init__` functions (as
`dataclasses` generates `__init__`); `_Codec` holds them on the class.
Nothing interprets `fields` per message.

A `lazy` sub-message is decoded when it is first read: `decode` checks
its framing and keeps its slice of the buffer (`_Unread`), so a caller
that reads a header out of a light block pays for no validator set.

The decode source has a second target (`Message.decoder_to`): the same
field loop, from the same generator, ending in a call of a builder the
caller names instead of in a message. A caller that wants its own
objects out of a buffer (types/validator_set.py, types/block.py) is
handed the decoded values once, "decoded once": no message is made to be
copied from and thrown away. What such a decoder accepts, skips and
refuses on any buffer is what `decode` does, because the loop is the
same text; only what is made at the end differs.
"""

from __future__ import annotations

import keyword
import threading

from . import wire

# ftype -> (wire type, zero value, kind); the kind selects the generated code.
_SCALARS = {
    "int32": (wire.WIRE_VARINT, 0, "signed"),
    "int64": (wire.WIRE_VARINT, 0, "signed"),
    "uint32": (wire.WIRE_VARINT, 0, "unsigned"),
    "uint64": (wire.WIRE_VARINT, 0, "unsigned"),
    "enum": (wire.WIRE_VARINT, 0, "unsigned"),
    "bool": (wire.WIRE_VARINT, False, "bool"),
    "sint32": (wire.WIRE_VARINT, 0, "zigzag"),
    "sint64": (wire.WIRE_VARINT, 0, "zigzag"),
    "sfixed64": (wire.WIRE_FIXED64, 0, "fixed64"),
    "fixed64": (wire.WIRE_FIXED64, 0, "fixed64"),
    "sfixed32": (wire.WIRE_FIXED32, 0, "fixed32"),
    "fixed32": (wire.WIRE_FIXED32, 0, "fixed32"),
    "bytes": (wire.WIRE_BYTES, b"", "bytes"),
    "string": (wire.WIRE_BYTES, "", "string"),
}


class Field:
    __slots__ = ("number", "ftype", "name", "repeated", "always_emit", "msg_cls", "lazy")

    def __init__(self, number, ftype, name, repeated=False, always_emit=False, msg_cls=None, lazy=False):
        self.number = number
        self.ftype = ftype
        self.name = name
        self.repeated = repeated
        # always_emit mirrors gogoproto (gogoproto.nullable) = false on
        # embedded messages: the field is marshaled unconditionally.
        self.always_emit = always_emit
        self.msg_cls = msg_cls  # class or callable returning class (for cycles)
        # a nullable sub-message whose inside `decode` leaves as bytes until the field is read
        self.lazy = lazy

    def message_class(self):
        cls = self.msg_cls
        if cls is not None and not isinstance(cls, type):
            cls = cls()  # lazy thunk for recursive schemas
        return cls


def _scalar(ftype: str):
    try:
        return _SCALARS[ftype]
    except KeyError:
        raise TypeError(f"unknown scalar type {ftype}") from None


class Deferred:
    """What a `DeferredAttr` holds until it is first read."""

    __slots__ = ()

    def read(self):
        raise NotImplementedError


class DeferredAttr:
    """An attribute that may be set to a `Deferred`, which the first
    read replaces by what it reads: from then on the attribute is an
    ordinary object, and what the `Deferred` held is let go. Two threads
    that read at once both read it, and both results are equal. A read
    that raises leaves the attribute as it was."""

    def __init__(self, name: str):
        self.slot = "_" + name

    def __get__(self, obj, owner=None):
        if obj is None:
            raise AttributeError(self.slot)  # as a dataclass field: no default
        v = obj.__dict__[self.slot]
        if isinstance(v, Deferred):
            v = obj.__dict__[self.slot] = v.read()
        return v

    def __set__(self, obj, value):
        obj.__dict__[self.slot] = value


class _Unread(Deferred):
    """A lazy field's sub-message as it lies in the buffer its parent
    was decoded from: framing checked, inside not yet looked at. Bytes
    that are no message raise on `read` what `decode` raises on them."""

    __slots__ = ("cls", "buf", "start", "end")

    def __init__(self, cls, buf: bytes, start: int, end: int):
        self.cls, self.buf, self.start, self.end = cls, buf, start, end

    def read(self):
        return self.cls.decode(self.buf[self.start : self.end])


def held(msg: Message, name: str):
    """What the lazy field `name` of `msg` holds as it stands, reading
    nothing: the `_Unread` that `decode` left (`buf`, `start`, `end`), or
    the message or None that it was read as or set to."""
    return msg.__dict__["_" + name]


class Message:
    """Base class; subclasses set `fields = [Field(...), ...]`."""

    fields: list[Field] = []
    _codec: _Codec | None = None

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        cls._codec = None  # a subclass never runs its parent's plan
        for f in cls.__dict__.get("fields", ()):
            if f.lazy:
                if f.ftype != "message" or f.repeated or f.always_emit:
                    raise TypeError(f"{cls.__name__}.{f.name}: only a nullable sub-message can be lazy")
                setattr(cls, f.name, DeferredAttr(f.name))

    def __init__(self, **kwargs):
        cls = type(self)
        (cls._codec or _codec_of(cls)).init(self, kwargs)

    # -- encoding ---------------------------------------------------------

    def encode(self) -> bytes:
        cls = type(self)
        return (cls._codec or _codec_of(cls)).encode(self)

    def encode_delimited(self) -> bytes:
        return wire.marshal_delimited(self.encode())

    @classmethod
    def encode_field(cls, name: str, value) -> bytes:
        """What `encode` emits for the field `name` alone when it holds `value`."""
        return (cls._codec or _codec_of(cls)).field_encoder(name)(value)

    # -- decoding ---------------------------------------------------------

    @classmethod
    def decode(cls, buf: bytes):
        if buf.__class__ is not bytes:
            buf = bytes(buf)  # slices of it become the `bytes` fields
        return (cls._codec or _codec_of(cls)).decode(buf, 0, len(buf))

    @classmethod
    def decode_delimited(cls, buf: bytes, offset: int = 0):
        body, pos = wire.unmarshal_delimited(buf, offset)
        return cls.decode(body), pos

    @classmethod
    def decoder_to(cls, build, **subs):
        """A `decode(buf, pos, end)` that reads the `cls` lying in
        `buf[pos:end]` (`buf` is `bytes`) as `cls.decode` reads it and
        returns `build(v0, v1, ...)`, one value a field in the order of
        `fields`, where `decode` would make a `cls` and set them on it.
        `subs` gives, by field name, the decoder that reads a message
        field's sub-message in place, as a rule another class's
        `decoder_to`; a sub-message that has one and that the buffer does
        not carry is None. A message field not named is decoded into its
        message."""
        return (cls._codec or _codec_of(cls)).decoder_to(build, subs)

    # -- niceties ---------------------------------------------------------

    def __eq__(self, other):
        if type(self) is not type(other):
            return NotImplemented
        return all(getattr(self, f.name) == getattr(other, f.name) for f in type(self).fields)

    def __repr__(self):
        parts = ", ".join(f"{f.name}={getattr(self, f.name)!r}" for f in type(self).fields)
        return f"{type(self).__name__}({parts})"

    def which(self) -> str | None:
        """For oneof-shaped messages: the name of the (single) set
        message field, or None. Usable by any envelope whose fields are
        mutually exclusive submessages."""
        for f in type(self).fields:
            if f.ftype == "message" and getattr(self, f.name) is not None:
                return f.name
        return None

    def copy(self):
        return type(self).decode(self.encode())


# -- the per-class plan -----------------------------------------------------

_compile_lock = threading.Lock()


def _codec_of(cls) -> _Codec:
    with _compile_lock:
        if cls._codec is None:
            cls._codec = _Codec(cls)
        return cls._codec


def _skip(buf: bytes, pos: int, end: int, wt: int) -> int:
    if wt == wire.WIRE_VARINT:
        return wire.decode_varint(buf, pos, end)[1]
    if wt == wire.WIRE_FIXED64:
        return pos + 8
    if wt == wire.WIRE_FIXED32:
        return pos + 4
    if wt == wire.WIRE_BYTES:
        n, pos = wire.decode_varint(buf, pos, end)
        if pos + n > end:
            raise ValueError("truncated length-delimited field")
        return pos + n
    raise ValueError(f"cannot skip wire type {wt}")


class _Codec:
    """The decode, encode and init functions generated from one class's `fields`.

    `decode(buf, pos, end)` reads a message in place, so a nested message
    is never copied out of its parent's buffer; `encode(msg)` joins the
    message's parts once; `init(msg, kwargs)` is `Message.__init__`. A
    sub-message class is compiled when the first message of it is met:
    until then the name the generated code calls is a stub that compiles
    the class and rebinds itself, which is also what lets recursive
    schemas resolve. `decoder_to` compiles the decode source's second
    target, for the classes that are asked for one.
    """

    def __init__(self, cls):
        for f in cls.fields:
            if not f.name.isidentifier() or keyword.iskeyword(f.name):
                raise TypeError(f"{cls.__name__}: field name {f.name!r} is not an identifier")
        self.cls = cls
        self._field_encoders = {}
        self._ns = {
            "cls": cls,
            "new": object.__new__,
            "varint": wire.decode_varint,
            "uvarint": wire.encode_varint,
            "skip": _skip,
            "fixed64": wire.decode_fixed64,
            "fixed32": wire.decode_fixed32,
            "ufixed64": wire.encode_fixed64,
            "ufixed32": wire.encode_fixed32,
            "uzigzag": wire.encode_zigzag,
            "B1": wire.ONE_BYTE,
            "join": b"".join,
            "Unread": _Unread,
        }
        for i, f in enumerate(cls.fields):
            if f.ftype == "message":
                self._bind_sub(i, f.message_class())
        self.decode = self._compile("decode", self._decode_source())
        self.encode = self._compile("encode", self._encode_source())
        self.init = self._compile("init", self._init_source())
        self._decoder_makers = {}

    def _compile(self, name: str, source: str):
        code = compile(source, f"<{self.cls.__module__}.{self.cls.__qualname__} codec>", "exec")
        exec(code, self._ns)  # noqa: S102 - source is generated from `fields` alone
        return self._ns.pop(name)

    def _bind_sub(self, i: int, sub: type) -> None:
        """Field i's message class as `M{i}`, and its codec's functions as
        `dec{i}` and `enc{i}` unless the class has a `decode` or `encode`
        of its own, which is then what the generated code calls."""
        ns = self._ns
        ns[f"M{i}"] = sub
        for key, attr, own in (
            (f"dec{i}", "decode", sub.decode.__func__ is not Message.decode.__func__),
            (f"enc{i}", "encode", sub.encode is not Message.encode),
        ):
            if own:
                continue

            def stub(*args, key=key, attr=attr):
                fn = ns[key] = getattr(sub._codec or _codec_of(sub), attr)
                return fn(*args)

            ns[key] = stub

    def _absent(self, i: int, f: Field) -> str:
        """What field i holds when nothing set it: a fresh list, a fresh
        `always_emit` message, None for a nullable one, the scalar's zero."""
        if f.repeated:
            return "[]"
        if f.ftype == "message":
            return f"M{i}()" if f.always_emit else "None"
        return repr(_scalar(f.ftype)[1])

    def _init_source(self) -> str:
        out = ["def init(self, kwargs):", "    pop = kwargs.pop"]
        for i, f in enumerate(self.cls.fields):
            absent = self._absent(i, f)
            out.append(f"    v = pop({f.name!r}, None)")
            out.append(f"    self.{f.name} = v" if absent == "None" else f"    self.{f.name} = {absent} if v is None else v")
        out.append("    if kwargs:")
        out.append("        raise TypeError(f'{cls.__name__}: unknown fields {sorted(kwargs)}')")
        return "\n".join(out)

    # -- decode -------------------------------------------------------------

    def decoder_to(self, build, subs: dict):
        """`Message.decoder_to`: the source is compiled once for each set
        of names in `subs`, as a function of `build` and the decoders."""
        fields = self.cls.fields
        at = tuple(i for i, f in enumerate(fields) if f.name in subs and f.ftype == "message" and not f.lazy)
        for name in subs.keys() - {fields[i].name for i in at}:
            raise TypeError(f"{self.cls.__name__}.{name}: only a sub-message that is not lazy is read by a decoder")
        with _compile_lock:  # `_compile` passes the function through the one namespace
            make = self._decoder_makers.get(at)
            if make is None:
                lines = [f"def make({', '.join(['build'] + [f'sub{i}' for i in at])}):"]
                lines += ["    " + line for line in self._decode_source(at).split("\n")] + ["    return decode"]
                make = self._decoder_makers[at] = self._compile("make", "\n".join(lines))
        return make(build, *(subs[fields[i].name] for i in at))

    def _decode_source(self, subs: tuple | None = None) -> str:
        """The source of `decode(buf, pos, end)`. With `subs`, the fields
        that `sub{i}` decodes in place, the second target: the same lines
        up to the end of the loop, then `build(f0, f1, ...)`."""
        fields = self.cls.fields
        out = ["def decode(buf, pos, end):"]
        # a message the buffer does not carry is made after the loop, not before it and thrown away
        late = {i for i, f in enumerate(fields) if f.ftype == "message" and f.always_emit and not f.repeated}
        for i, f in enumerate(fields):
            out.append(f"    f{i} = None" if i in late else f"    f{i} = {self._absent(i, f)}")
        out += [
            "    while pos < end:",
            "        tag = buf[pos]",
            "        if tag < 128:",
            "            pos += 1",
            "        else:",
            "            tag, pos = varint(buf, pos, end)",
        ]
        # a field is chosen by its number alone; of two with one number the later
        by_number = {f.number: (i, f) for i, f in enumerate(fields)}
        if by_number:
            out.append("        num = tag >> 3")
        branch = "if"
        for number in sorted(by_number):
            out.append(f"        {branch} num == {number}:")
            out += self._decode_field_lines(*by_number[number], " " * 12, subs or ())
            branch = "elif"
        skip = "pos = skip(buf, pos, end, tag & 7)"
        out += ["        else:", f"            {skip}"] if by_number else [f"        {skip}"]
        if subs is not None:
            values = [f"M{i}() if f{i} is None else f{i}" if i in late and i not in subs else f"f{i}" for i in range(len(fields))]
            return "\n".join(out + [f"    return build({', '.join(values)})"])
        out.append("    msg = new(cls)")
        for i, f in enumerate(fields):
            out.append(f"    msg.{f.name} = M{i}() if f{i} is None else f{i}" if i in late else f"    msg.{f.name} = f{i}")
        out.append("    return msg")
        return "\n".join(out)

    def _decode_field_lines(self, i: int, f: Field, ind: str, subs: tuple = ()) -> list[str]:
        if f.ftype == "message":
            if i in subs:
                store = [ind + (f"f{i}.append(sub{i}(buf, pos, e))" if f.repeated else f"f{i} = sub{i}(buf, pos, e)")]
            elif f.lazy:
                # only the last occurrence is kept unread: one it replaces is decoded here, for its verdict
                store = [f"{ind}if f{i} is not None:", f"{ind}    f{i}.read()", f"{ind}f{i} = Unread(M{i}, buf, pos, e)"]
            else:
                value = f"dec{i}(buf, pos, e)" if f"dec{i}" in self._ns else f"M{i}.decode(buf[pos:e])"
                store = [ind + (f"f{i}.append({value})" if f.repeated else f"f{i} = {value}")]
            return _read_length(ind, "end") + store + [f"{ind}pos = e"]
        kind = _scalar(f.ftype)[2]
        if not f.repeated:
            return _read_scalar(kind, ind, "end", f"f{i}")
        single = _read_scalar(kind, ind, "end", "v") + [f"{ind}f{i}.append(v)"]
        if kind in ("bytes", "string"):  # every other scalar may come packed
            return single
        packed = _read_length(ind + "    ", "end")
        packed += [f"{ind}    while pos < e:"]
        packed += _read_scalar(kind, ind + " " * 8, "e", "v") + [f"{ind}        f{i}.append(v)"]
        packed.append(f"{ind}    pos = e")
        return [f"{ind}if tag & 7 == 2:"] + packed + [f"{ind}else:"] + [f"    {line}" for line in single]

    # -- encode -------------------------------------------------------------

    def _encode_source(self) -> str:
        fields = sorted(enumerate(self.cls.fields), key=lambda item: item[1].number)
        if not fields:
            return 'def encode(msg):\n    return b""'
        out = ["def encode(msg):", "    parts = []", "    add = parts.append"]
        for i, f in fields:
            # a lazy field's slot, not its attribute: encoding reads nothing
            out.append(f"    v = msg.__dict__[{'_' + f.name!r}]" if f.lazy else f"    v = msg.{f.name}")
            out += self._encode_field_lines(i, f)
        out.append("    return join(parts)")
        return "\n".join(out)

    def field_encoder(self, name: str):
        """encode(value) -> what `encode` emits for the field `name` alone."""
        enc = self._field_encoders.get(name)
        if enc is None:
            i, f = next((i, f) for i, f in enumerate(self.cls.fields) if f.name == name)
            lines = ["def encode_field(v):", "    parts = []", "    add = parts.append"]
            lines += self._encode_field_lines(i, f) + ["    return join(parts)"]
            enc = self._field_encoders[name] = self._compile("encode_field", "\n".join(lines))
        return enc

    def _encode_field_lines(self, i: int, f: Field) -> list[str]:
        """Lines that add field i's wire bytes for the value in `v`."""
        self._ns[f"T{i}"] = wire.encode_tag(f.number, wire.WIRE_BYTES)
        delimited = [f"add(T{i})", "n = len(b)", "add(B1[n] if n < 128 else uvarint(n))", "add(b)"]

        def each(convert: str, present: str) -> list[str]:
            # b = convert(x) for every x of a repeated field, or for v itself
            if f.repeated:
                body = ["if v:", "    for x in v:", "        b = " + convert.format(x="x")]
                body += [f"        {s}" for s in delimited]
            else:
                body = [f"if {present}:", "    b = " + convert.format(x="v")] + [f"    {s}" for s in delimited]
            return [f"    {s}" for s in body]

        if f.ftype == "message":
            # a present message is emitted even when empty (gogo writes
            # tag+len for non-nil pointers); `always_emit` only decides
            # what an absent one defaults to.
            convert = "{x}.encode()"
            if f"enc{i}" in self._ns:
                convert = f"enc{i}({{x}}) if {{x}}.__class__ is M{i} else {{x}}.encode()"
            if f.lazy:  # never read: the bytes it arrived as
                convert = f"{{x}}.buf[{{x}}.start:{{x}}.end] if {{x}}.__class__ is Unread else ({convert})"
            return each(convert, "v is not None")
        wt, zero, kind = _scalar(f.ftype)
        if kind == "string":
            return each("{x}.encode('utf-8')", f"v != {zero!r}")
        if kind == "bytes":
            return each("{x} if {x}.__class__ is bytes else bytes({x})", f"v != {zero!r}")
        payload = {
            "signed": "uvarint({x} if {x}.__class__ is int else int({x}))",
            "unsigned": "uvarint({x} if {x}.__class__ is int else int({x}))",
            "bool": "uvarint(int({x}))",
            "zigzag": "uzigzag(int({x}))",
            "fixed64": "ufixed64(int({x}))",
            "fixed32": "ufixed32(int({x}))",
        }[kind]
        if f.repeated:
            body = ["if v:", f"    b = join([{payload.format(x='x')} for x in v])"] + [f"    {s}" for s in delimited]
        else:
            self._ns[f"S{i}"] = wire.encode_tag(f.number, wt)
            body = [f"if v != {zero!r}:", f"    add(S{i})", f"    add({payload.format(x='v')})"]
        return [f"    {s}" for s in body]


def _read_varint(ind: str, var: str, end: str) -> list[str]:
    """One byte inline, the general loop for the rest; 128 stands for 'none left'."""
    return [
        f"{ind}{var} = buf[pos] if pos < {end} else 128",
        f"{ind}if {var} < 128:",
        f"{ind}    pos += 1",
        f"{ind}else:",
        f"{ind}    {var}, pos = varint(buf, pos, {end})",
    ]


def _read_length(ind: str, end: str) -> list[str]:
    return _read_varint(ind, "n", end) + [
        f"{ind}e = pos + n",
        f"{ind}if e > {end}:",
        f"{ind}    raise ValueError('truncated length-delimited field')",
    ]


def _read_scalar(kind: str, ind: str, end: str, v: str) -> list[str]:
    """Lines that read one scalar at `pos` into `v`. `end` bounds the
    buffer the scalar lies in: a fixed-width read that would cross it is
    refused like any other truncated field."""
    if kind in ("fixed64", "fixed32"):
        return [
            f"{ind}if pos + {8 if kind == 'fixed64' else 4} > {end}:",
            f"{ind}    raise ValueError('truncated {kind} field')",
            f"{ind}{v}, pos = {kind}(buf, pos)",
        ]
    if kind in ("bytes", "string"):
        tail = ".decode('utf-8')" if kind == "string" else ""
        return _read_length(ind, end) + [f"{ind}{v} = buf[pos:e]{tail}", f"{ind}pos = e"]
    lines = _read_varint(ind, v, end)
    if kind == "signed":  # int32 too: a negative one is a sign-extended 10-byte varint
        lines.append(f"{ind}    if {v} > 0x7FFFFFFFFFFFFFFF:")
        lines.append(f"{ind}        {v} -= 0x10000000000000000")
    elif kind == "bool":
        lines.append(f"{ind}{v} = {v} != 0")
    elif kind == "zigzag":
        lines.append(f"{ind}{v} = ({v} >> 1) ^ -({v} & 1)")
    return lines
