"""Fabric wiring and routing tables of the plain reference.

A frozen copy of the mesh, torus and multi-die builders of the FlooNoC
simulator (XY / shortest-direction table routing, west-edge HBM endpoints,
die-to-die repeater chains, dateline VC tables), kept with the benchmark so
that the yardstick does not move when the program does. Only what the
benchmark's configurations use is here.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

N, E, S, W, L = 0, 1, 2, 3, 4  # port ids
XE, XW, YN, YS = 5, 6, 7, 8  # express ports (span-k links), radix 9


@dataclass
class Fabric:
    """Router wiring, endpoint attachments and routing tables."""

    n_routers: int
    n_ports: int
    n_endpoints: int
    link_to: np.ndarray  # [R, P, 2] (dst router, dst port) or (-1, -1)
    ep_attach: np.ndarray  # [E, 2] (router, port)
    route: np.ndarray  # [R, E] output port toward each endpoint
    tile_coord: np.ndarray  # [E, 2] (x, y)
    n_tiles: int
    nx: int
    ny: int
    n_hbm: int = 0
    port_dim: np.ndarray | None = None  # [R, P] 0 = X, 1 = Y, 2 = local
    dateline: np.ndarray | None = None  # [R, P] wrap out-links
    meta: dict = field(default_factory=dict)

    @property
    def port_ep(self) -> np.ndarray:
        """[R, P] endpoint attached at each router port, or -1."""
        out = np.full((self.n_routers, self.n_ports), -1, np.int32)
        for e, (r, p) in enumerate(self.ep_attach):
            out[r, p] = e
        return out


def mesh(nx: int = 4, ny: int = 8, hbm_west: bool = True,
         express: int = 0) -> Fabric:
    """2-D mesh, XY routing; one HBM endpoint per row off the west edge."""
    R = nx * ny
    k = int(express)
    P = 9 if k > 0 else 5
    rid = lambda x, y: y * nx + x
    link_to = np.full((R, P, 2), -1, np.int32)
    for y in range(ny):
        for x in range(nx):
            r = rid(x, y)
            if y + 1 < ny:
                link_to[r, N] = (rid(x, y + 1), S)
            if y > 0:
                link_to[r, S] = (rid(x, y - 1), N)
            if x + 1 < nx:
                link_to[r, E] = (rid(x + 1, y), W)
            if x > 0:
                link_to[r, W] = (rid(x - 1, y), E)
            if k > 0:
                if x + k < nx:
                    link_to[r, XE] = (rid(x + k, y), XW)
                if x - k >= 0:
                    link_to[r, XW] = (rid(x - k, y), XE)
                if y + k < ny:
                    link_to[r, YN] = (rid(x, y + k), YS)
                if y - k >= 0:
                    link_to[r, YS] = (rid(x, y - k), YN)
    eps = [(rid(x, y), L) for y in range(ny) for x in range(nx)]
    n_tiles = len(eps)
    if hbm_west:
        eps += [(rid(0, y), W) for y in range(ny)]
    n_ep = len(eps)
    coord = np.array([(r % nx, r // nx) for r, _ in eps], np.int32)

    def step_x(x, ex):
        if ex > x:
            return XE if k > 0 and ex - x >= k and x + k < nx else E
        return XW if k > 0 and x - ex >= k and x - k >= 0 else W

    def step_y(y, ey):
        if ey > y:
            return YN if k > 0 and ey - y >= k and y + k < ny else N
        return YS if k > 0 and y - ey >= k and y - k >= 0 else S

    route = np.full((R, n_ep), -1, np.int32)
    for r in range(R):
        x, y = r % nx, r // nx
        for e in range(n_ep):
            er, port = eps[e]
            ex, ey = er % nx, er // nx
            if e >= n_tiles:  # HBM endpoint off the west port of (0, ey)
                if (x, y) == (0, ey):
                    route[r, e] = W
                    continue
                ex = 0
            if (x, y) == (ex, ey):
                route[r, e] = port
            elif x != ex:
                route[r, e] = step_x(x, ex)
            else:
                route[r, e] = step_y(y, ey)
    return Fabric(R, P, n_ep, link_to, np.array(eps, np.int32), route, coord,
                  n_tiles, nx, ny, n_hbm=ny if hbm_west else 0)


def torus(nx: int = 4, ny: int = 4) -> Fabric:
    """2-D torus, shortest-direction dimension-ordered routing (ties go
    East / North), with the dateline tables of the wrap links."""
    R, P = nx * ny, 5
    rid = lambda x, y: y * nx + x
    link_to = np.full((R, P, 2), -1, np.int32)
    for y in range(ny):
        for x in range(nx):
            r = rid(x, y)
            if ny > 1:
                link_to[r, N] = (rid(x, (y + 1) % ny), S)
                link_to[r, S] = (rid(x, (y - 1) % ny), N)
            if nx > 1:
                link_to[r, E] = (rid((x + 1) % nx, y), W)
                link_to[r, W] = (rid((x - 1) % nx, y), E)
    eps = [(rid(x, y), L) for y in range(ny) for x in range(nx)]
    coord = np.array([(r % nx, r // nx) for r, _ in eps], np.int32)
    route = np.full((R, len(eps)), -1, np.int32)
    for r in range(R):
        x, y = r % nx, r // nx
        for e, (er, port) in enumerate(eps):
            ex, ey = er % nx, er // nx
            if (x, y) == (ex, ey):
                route[r, e] = port
            elif x != ex:
                dx = (ex - x) % nx
                route[r, e] = E if dx <= nx - dx else W
            else:
                dy = (ey - y) % ny
                route[r, e] = N if dy <= ny - dy else S
    port_dim = np.full((R, P), -1, np.int32)
    port_dim[:, [E, W]] = 0
    port_dim[:, [N, S]] = 1
    port_dim[:, L] = 2
    dateline = np.zeros((R, P), bool)
    for y in range(ny):
        for x in range(nx):
            r = rid(x, y)
            if nx > 1:
                dateline[r, E] = x == nx - 1
                dateline[r, W] = x == 0
            if ny > 1:
                dateline[r, N] = y == ny - 1
                dateline[r, S] = y == 0
    return Fabric(R, P, len(eps), link_to, np.array(eps, np.int32), route,
                  coord, len(eps), nx, ny, port_dim=port_dim,
                  dateline=dateline)


def multi_die(n_dies: int = 2, nx: int = 4, ny: int = 4,
              d2d: int = 3) -> Fabric:
    """``n_dies`` mesh dies stitched along X; each boundary row link runs
    through ``d2d`` 1-in/1-out repeater routers. Global XY routing."""
    NX = n_dies * nx
    R0, P = NX * ny, 5
    rid = lambda gx, y: y * NX + gx
    links = []
    routers = R0
    east_of = {}  # repeater -> first global column east of it
    for y in range(ny):
        for gx in range(NX):
            r = rid(gx, y)
            if y + 1 < ny:
                links.append((r, N, rid(gx, y + 1), S))
            if gx + 1 < NX and (gx + 1) % nx != 0:
                links.append((r, E, rid(gx + 1, y), W))
    for d in range(1, n_dies):
        bx = d * nx
        for y in range(ny):
            prev, pp = rid(bx - 1, y), E
            for c in range(routers, routers + d2d):
                east_of[c] = bx
                links.append((prev, pp, c, 0))
                prev, pp = c, 1
            routers += d2d
            links.append((prev, pp, rid(bx, y), W))
    link_to = np.full((routers, P, 2), -1, np.int32)
    for r1, p1, r2, p2 in links:
        link_to[r1, p1] = (r2, p2)
        link_to[r2, p2] = (r1, p1)
    eps = [(rid(gx, y), L) for y in range(ny) for gx in range(NX)]
    coord = np.array([(r % NX, r // NX) for r, _ in eps], np.int32)
    route = np.full((routers, len(eps)), -1, np.int32)
    for r in range(R0):
        x, y = r % NX, r // NX
        for e, (er, port) in enumerate(eps):
            ex, ey = er % NX, er // NX
            if (x, y) == (ex, ey):
                route[r, e] = port
            elif x != ex:
                route[r, e] = E if ex > x else W
            else:
                route[r, e] = N if ey > y else S
    for rep, bx in east_of.items():
        for e, (er, _) in enumerate(eps):
            route[rep, e] = 1 if er % NX >= bx else 0
    return Fabric(routers, P, len(eps), link_to, np.array(eps, np.int32),
                  route, coord, len(eps), NX, ny)


BUILDERS = {"mesh": mesh, "torus": torus, "multi_die": multi_die}

# the shape fields of a configuration that each builder takes
SHAPE_FIELDS = ("nx", "ny", "hbm_west", "express", "n_dies", "d2d")


def build(fabric: dict) -> Fabric:
    """The fabric a configuration's ``fabric`` block describes."""
    kw = {k: v for k, v in fabric.items() if k in SHAPE_FIELDS and v is not None}
    return BUILDERS[fabric.get("topology", "mesh")](**kw)
