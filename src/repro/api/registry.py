"""String-keyed backend registry + the ``make_index`` factory.

``register_backend`` installs a ``BackendSpec`` under a name;
``make_index("deltatree", initial=keys, height=7, ...)`` builds the
backend's (cfg, state) pair and wraps it in an ``Index`` handle.  New
comparison structures (non-blocking interpolation search trees,
elimination (a,b)-trees, ...) plug in as registry entries — no new façade,
no call-site changes.
"""

from __future__ import annotations

from repro.api.index import BackendSpec, Index, IndexSpec
from repro.obs import trace as OT

_REGISTRY: dict[str, BackendSpec] = {}


def register_backend(spec: BackendSpec, *, overwrite: bool = False) -> BackendSpec:
    """Install ``spec`` under ``spec.name``; re-registration must opt in."""
    if spec.name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {spec.name!r} already registered")
    _REGISTRY[spec.name] = spec
    return spec


def get_backend(name: str) -> BackendSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown backend {name!r}; registered: {available_backends()}"
        ) from None


def available_backends() -> list[str]:
    return sorted(_REGISTRY)


def supported_maintenance(backend: str) -> tuple[str, ...]:
    """Maintenance policy *kinds* ``backend`` accepts via ``maintenance=``.

    ``("*",)`` expands to every kind the scheduler knows
    (``repro.maintenance.KINDS``); literal entries pass through."""
    spec = get_backend(backend)
    if "*" not in spec.maintenance:
        return spec.maintenance
    from repro.maintenance import KINDS

    literal = [m for m in spec.maintenance if m != "*"]
    return tuple(dict.fromkeys(literal + list(KINDS)))


def supported_engines(backend: str) -> tuple[str, ...]:
    """Live SearchEngine names ``backend`` accepts via ``engine=``.

    A declared ``"*"`` entry expands to the ``repro.core.engine`` registry
    *at call time* (engines registered after import are selectable);
    literal names pass through unchanged, so a backend with a private
    read path is pinned to exactly what it declared."""
    spec = get_backend(backend)
    if "*" not in spec.engines:
        return spec.engines
    from repro.core.engine import available_engines

    literal = [e for e in spec.engines if e != "*"]
    return tuple(dict.fromkeys(literal + available_engines()))


def make_index(backend: str = "deltatree", *, initial=None, payloads=None,
               engine: str | None = None, maintenance: str | None = None,
               **kwargs) -> Index:
    """Build an Index: ``backend`` picks the registry entry, ``initial``
    (unique keys) and ``payloads`` seed a bulk build (empty when None),
    ``engine`` selects the read-path SearchEngine ("scalar" / "lockstep";
    validated against the backend's declared ``engines``; the sentinel
    ``"auto"`` resolves to the committed bench-table winner for this
    backend + execution mode first — ``core.engine.resolve_engine``),
    ``maintenance`` the scheduler policy ("eager" / "deferred" /
    "budgeted:K"; validated against the backend's declared policy kinds),
    remaining kwargs go to the backend's config (e.g. ``height=7`` or a
    prebuilt ``cfg=...``).  The build runs under the ``index.build``
    span (``repro.obs.trace``)."""
    from repro.maintenance import parse_policy

    spec = get_backend(backend)
    if engine == "auto":
        from repro.core.engine import resolve_engine

        engine = resolve_engine(engine, backend)
        if engine not in supported_engines(backend):
            engine = "scalar"  # table winner the backend can't run
    if engine is not None:
        engines = supported_engines(backend)
        if engine not in engines:
            raise ValueError(
                f"backend {backend!r} supports engines {engines}, "
                f"not {engine!r}")
        if spec.engines != ("scalar",):
            # engine-aware backends thread the name into their TreeConfig;
            # single-engine backends just validated the default above
            kwargs["engine"] = engine
    if maintenance is not None:
        pol = parse_policy(maintenance)   # ValueError on garbage specs
        kinds = supported_maintenance(backend)
        if pol.kind not in kinds:
            raise ValueError(
                f"backend {backend!r} supports maintenance policies "
                f"{kinds}, not {maintenance!r}")
        if spec.maintenance != ("eager",):
            kwargs["maintenance"] = str(pol)
    with OT.span("index.build"):
        cfg, state = spec.make(initial, payloads, **kwargs)
    ix = Index(IndexSpec(backend=spec, cfg=cfg), state)
    if ix.engine not in supported_engines(backend):
        # catches engine typos smuggled in via a prebuilt cfg= (e.g.
        # TreeConfig(engine=...) / PagerConfig.engine) at construction
        # time instead of as a KeyError at the first read
        raise ValueError(
            f"backend {backend!r} config names engine {ix.engine!r}; "
            f"supported: {supported_engines(backend)}")
    # same early validation for policies smuggled in via a prebuilt cfg=
    ix_pol = parse_policy(ix.maintenance)
    if ix_pol.kind not in supported_maintenance(backend):
        raise ValueError(
            f"backend {backend!r} config names maintenance policy "
            f"{ix.maintenance!r}; supported kinds: "
            f"{supported_maintenance(backend)}")
    if payloads is not None and not ix.capability.map_mode:
        raise ValueError(
            f"backend {backend!r} with {ix.capability} stores no payloads; "
            f"drop payloads= or configure map mode (e.g. payload_bits > 0)")
    return ix
