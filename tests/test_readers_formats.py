"""Round-3 format readers vs the reference's own binary fixtures.

Reference: `datasource/OGRFileFormat.scala:26` (any OGR driver),
`core/raster/MosaicRasterGDAL.scala:182-187` (any GDAL raster), fixtures
at `src/test/resources/binary/{grib-cams,zarr-example}`.
"""

import glob
import os

import numpy as np
import pytest

from mosaic_tpu.readers import (
    read,
    read_geopackage,
    read_grib2,
    read_zarr,
    write_geopackage,
)

GRIB_DIR = "/root/reference/src/test/resources/binary/grib-cams"
ZARR_ZIP = "/root/reference/src/test/resources/binary/zarr-example/zarr_test_data.zip"

needs_fixtures = pytest.mark.skipif(
    not os.path.isdir(GRIB_DIR), reason="reference fixtures unavailable"
)


# ------------------------------------------------------------------- GRIB2
@needs_fixtures
def test_grib_all_fixtures_decode():
    files = sorted(glob.glob(f"{GRIB_DIR}/*.grib"))
    assert len(files) == 3
    for p in files:
        r = read_grib2(p)
        # 6 GRIB2 + 8 GRIB1 messages per file, one band each (as GDAL does)
        assert r.num_bands == 14 and r.data.shape == (14, 14, 14)
        assert r.srid == 4326
        # CAMS aerosol mixing ratios: positive, tiny
        assert 0 < np.nanmin(r.data) and np.nanmax(r.data) < 1e-3
        # regular 0.75-degree lat/lon grid over north Africa
        x0, dx, _, y0, _, dy = r.gt
        assert dx == pytest.approx(0.75) and dy == pytest.approx(-0.75)
        assert y0 == pytest.approx(10.125) and x0 == pytest.approx(-0.375)


@needs_fixtures
def test_grib_matches_gdal_statistics():
    """Band min/max must reproduce the STATISTICS_* values GDAL itself
    computed into the fixture's .aux.xml sidecar — an independent oracle."""
    import re

    p = glob.glob(f"{GRIB_DIR}/*1650626995*.grib")[0]
    xml = open(p + ".aux.xml").read()
    mins = sorted(float(v) for v in re.findall(r'STATISTICS_MINIMUM">([^<]+)', xml))
    maxs = sorted(float(v) for v in re.findall(r'STATISTICS_MAXIMUM">([^<]+)', xml))
    r = read_grib2(p)
    got_min = sorted(float(r.data[b].min()) for b in range(r.num_bands))
    got_max = sorted(float(r.data[b].max()) for b in range(r.num_bands))
    np.testing.assert_allclose(got_min, mins, rtol=1e-9)
    np.testing.assert_allclose(got_max, maxs, rtol=1e-9)


@needs_fixtures
def test_grib_through_read_raster_and_rst():
    from mosaic_tpu.raster import read_raster

    p = sorted(glob.glob(f"{GRIB_DIR}/*.grib"))[0]
    r = read_raster(p)  # extension dispatch
    assert r.num_bands == 14
    # rst_* surface applies to grib rasters unchanged
    from mosaic_tpu.functions import raster as R

    assert R.rst_numbands(r) == 14
    wx, wy = r.raster_to_world(0, 0)
    assert wx == pytest.approx(r.gt[0]) and wy == pytest.approx(r.gt[3])


def test_grib_rejects_garbage(tmp_path):
    p = tmp_path / "bad.grib"
    p.write_bytes(b"GRIB" + b"\x00" * 40)
    with pytest.raises(ValueError):
        read_grib2(str(p))


# -------------------------------------------------------------------- Zarr
@needs_fixtures
def test_zarr_fixture_arrays():
    store_arrays = {
        "group_with_dims/var2D": (20, 20),
        "group_with_dims/var3D": (20, 20, 20),
        "group_with_attrs/F_order_array": (20, 20),
        "group_with_attrs/nested": (20, 20),
    }
    for name, shape in store_arrays.items():
        arr, _attrs = read_zarr(ZARR_ZIP, array=name)
        assert arr.shape == shape, name
    # C vs F order must decode to the same logical values
    a, _ = read_zarr(ZARR_ZIP, array="group_with_dims/var2D")
    f, _ = read_zarr(ZARR_ZIP, array="group_with_attrs/F_order_array")
    assert a.dtype == np.int32
    # var2D rows are 0..19 repeated (written by the fixture generator)
    assert (a[0] == np.arange(20)).all()


@needs_fixtures
def test_zarr_missing_chunks_use_fill():
    arr, _ = read_zarr(ZARR_ZIP, array="group_with_attrs/partial_fill1")
    assert (arr == 999.0).any() and arr.dtype == np.float32


@needs_fixtures
def test_zarr_via_registry():
    arr, attrs = read("zarr").option("array", "group_with_dims/var1D").load(ZARR_ZIP)
    assert arr.shape == (20,)


def test_zarr_directory_store(tmp_path):
    import json

    d = tmp_path / "store"
    (d / "a").mkdir(parents=True)
    (d / "a" / ".zarray").write_text(
        json.dumps(
            {
                "zarr_format": 2,
                "shape": [4, 6],
                "chunks": [2, 3],
                "dtype": "<f8",
                "order": "C",
                "fill_value": -1.0,
                "compressor": {"id": "zlib", "level": 1},
                "filters": None,
            }
        )
    )
    import zlib

    block = np.arange(6, dtype=np.float64).reshape(2, 3)
    (d / "a" / "0.0").write_bytes(zlib.compress(block.tobytes()))
    arr, _ = read_zarr(str(d), array="a")
    np.testing.assert_array_equal(arr[:2, :3], block)
    assert (arr[2:, :] == -1.0).all()  # missing chunks -> fill


# -------------------------------------------------------------- GeoPackage
def test_geopackage_roundtrip(tmp_path):
    from mosaic_tpu.core.geometry import wkt as W
    from mosaic_tpu.readers.vector import VectorTable

    wkts = [
        "POLYGON ((1 1, 13 2, 12 11, 6 14, 2 9, 1 1), (5 5, 5 8, 8 8, 8 5, 5 5))",
        "MULTIPOLYGON (((-20 -20, -12 -20, -12 -12, -20 -12, -20 -20)))",
        "POINT (5 5)",
        "LINESTRING (0 0, 3 4, 6 0)",
    ]
    col = W.from_wkt(wkts)
    vt = VectorTable(
        geometry=col, columns={"score": np.asarray([1.0, 2.5, -3.0, 0.0])}
    )
    p = tmp_path / "zones.gpkg"
    write_geopackage(str(p), vt, layer="zones", srid=4326)
    back = read_geopackage(str(p))
    assert len(back.geometry) == 4
    assert back.columns["score"].tolist() == [1.0, 2.5, -3.0, 0.0]
    # geometry-exact roundtrip
    got = W.to_wkt(back.geometry)
    want = W.to_wkt(col)
    assert got == want
    assert (np.asarray(back.geometry.srid) == 4326).all()


def test_geopackage_layer_listing_and_errors(tmp_path):
    from mosaic_tpu.core.geometry import wkt as W
    from mosaic_tpu.readers.geopackage import list_layers
    from mosaic_tpu.readers.vector import VectorTable

    col = W.from_wkt(["POINT (0 0)"])
    p = tmp_path / "one.gpkg"
    write_geopackage(str(p), VectorTable(geometry=col, columns={}), layer="pts")
    assert list_layers(str(p)) == ["pts"]
    with pytest.raises(ValueError):
        read_geopackage(str(p), layer="absent")


def test_geopackage_envelope_flag_variants(tmp_path):
    """Blobs with a 32-byte envelope (flag code 1) must parse too."""
    import sqlite3
    import struct

    from mosaic_tpu.core.geometry import wkt as W
    from mosaic_tpu.core.geometry import wkb as B
    from mosaic_tpu.readers.vector import VectorTable

    col = W.from_wkt(["POINT (7 8)"])
    p = tmp_path / "env.gpkg"
    write_geopackage(str(p), VectorTable(geometry=col, columns={}), layer="pts")
    con = sqlite3.connect(str(p))
    w = B.to_wkb(col)[0]
    blob = (
        b"GP\x00\x03"  # flags: envelope code 1 | little-endian
        + struct.pack("<i", 4326)
        + struct.pack("<4d", 7.0, 7.0, 8.0, 8.0)
        + w
    )
    con.execute('UPDATE "pts" SET geom=?', (blob,))
    con.commit()
    con.close()
    back = read_geopackage(str(p))
    assert W.to_wkt(back.geometry) == ["POINT (7 8)"]


# ----------------------------------------------------------- NetCDF-4/HDF5
NC_DIR = "/root/reference/src/test/resources/binary/netcdf-coral"


@needs_fixtures
def test_netcdf_coral_decode():
    """NOAA CRW 5km coral product: global 0.05-degree uint8 grids."""
    from mosaic_tpu.readers import H5Lite

    p = sorted(glob.glob(f"{NC_DIR}/*.nc"))[0]
    h5 = H5Lite(p)
    assert set(h5.datasets()) == {
        "bleaching_alert_area", "crs", "lat", "lon", "mask", "time",
    }
    lat = h5.read("lat")
    lon = h5.read("lon")
    assert lat.shape == (3600,) and lon.shape == (7200,)
    np.testing.assert_allclose(lat[0], 89.975)
    np.testing.assert_allclose(lat[-1], -89.975)
    np.testing.assert_allclose(lon[0], -179.975)
    baa = h5.read("bleaching_alert_area")
    assert baa.shape == (1, 3600, 7200) and baa.dtype == np.uint8
    assert h5.fill_value("bleaching_alert_area") == 251
    vals = set(np.unique(baa).tolist())
    assert vals <= {0, 1, 2, 3, 4, 251}  # alert levels + fill


@needs_fixtures
def test_netcdf_all_fixture_files_consistent():
    """Every day of the coral series decodes to the same grid."""
    from mosaic_tpu.readers import read_netcdf

    for p in sorted(glob.glob(f"{NC_DIR}/*.nc"))[:4]:
        r = read_netcdf(p)
        assert r.data.shape == (2, 3600, 7200)
        # coordinate variables are f32: compare to f32 precision
        np.testing.assert_allclose(
            r.gt, (-180.0, 0.05, 0.0, 90.0, 0.0, -0.05), atol=1e-4
        )
        assert 0.5 < float(np.isfinite(r.data).mean()) <= 1.0


@needs_fixtures
def test_netcdf_via_read_raster_and_registry():
    from mosaic_tpu.raster import read_raster

    p = sorted(glob.glob(f"{NC_DIR}/*.nc"))[0]
    r = read_raster(p)  # .nc extension dispatch
    assert r.num_bands == 2
    r2 = read("netcdf").option("variable", "mask").load(p)
    assert r2.num_bands == 1
    from mosaic_tpu.functions import raster as R

    assert int(R.rst_width([r])[0]) == 7200


def test_netcdf_rejects_non_hdf5(tmp_path):
    from mosaic_tpu.readers import H5Lite

    p = tmp_path / "no.nc"
    p.write_bytes(b"CDF\x01" + b"\x00" * 64)  # netCDF-3 classic
    with pytest.raises(ValueError):
        H5Lite(str(p))


# ------------------------------------------------------------ ESRI FileGDB
GDB_ZIP = "/root/reference/src/test/resources/binary/geodb/bridges.gdb.zip"


@needs_fixtures
def test_filegdb_bridges_fixture():
    """All 19,890 NYSDOT bridges decode; geometry agrees with the
    fixture's own LATITUDE/LONGITUDE attribute columns after UTM->WGS84
    (our CRS stack) for >90% of rows at <1e-6 deg (the rest are source
    data discrepancies — the median error is ~4e-9 deg)."""
    from mosaic_tpu.core import crs
    from mosaic_tpu.readers import read_filegdb

    vt = read_filegdb(GDB_ZIP)
    assert len(vt.geometry) == 19890
    assert len(vt.columns) == 41
    n = 2000
    xy = np.stack([vt.geometry.geom_xy(i)[0] for i in range(n)])
    ll = crs.to_wgs84(xy, 26918, np)
    lat, lon = vt.columns["LATITUDE"][:n], vt.columns["LONGITUDE"][:n]
    ok = np.isfinite(lat) & np.isfinite(lon)
    err = np.hypot(ll[ok, 1] - lat[ok], ll[ok, 0] - lon[ok])
    assert np.median(err) < 1e-7
    assert (err < 1e-6).mean() > 0.85
    # attribute columns decode with real content
    assert "STEUBEN" in set(
        v for v in vt.columns["COUNTY_NAME"][:50] if v is not None
    )


@needs_fixtures
def test_filegdb_layer_listing_and_registry():
    import tempfile
    import zipfile

    from mosaic_tpu.readers.filegdb import list_gdb_layers

    tmp = tempfile.mkdtemp()
    with zipfile.ZipFile(GDB_ZIP) as z:
        z.extractall(tmp)
    gdb = os.path.join(tmp, "NYSDOTBridges.gdb")
    assert list(list_gdb_layers(gdb)) == ["Bridges_Feb2019"]
    vt = read("geodb").option("layer", "Bridges_Feb2019").load(gdb)
    assert len(vt.geometry) == 19890
    with pytest.raises(ValueError):
        read("geodb").option("layer", "nope").load(gdb)


# ----------------------------------------------------------------- KML
_KML_DOC = """<?xml version="1.0" encoding="UTF-8"?>
<kml xmlns="http://www.opengis.net/kml/2.2">
 <Document>
  <Folder>
   <Placemark>
    <name>hq</name>
    <ExtendedData><Data name="kind"><value>office</value></Data></ExtendedData>
    <Point><coordinates>-73.98,40.75,12.5</coordinates></Point>
   </Placemark>
   <Placemark>
    <name>route</name>
    <LineString><coordinates>
      -74.0,40.7 -73.95,40.72 -73.9,40.76
    </coordinates></LineString>
   </Placemark>
  </Folder>
  <Placemark>
   <name>zone</name>
   <ExtendedData><SchemaData><SimpleData name="code">Z1</SimpleData></SchemaData></ExtendedData>
   <Polygon>
    <outerBoundaryIs><LinearRing><coordinates>
      -74.02,40.70 -73.96,40.70 -73.96,40.76 -74.02,40.76 -74.02,40.70
    </coordinates></LinearRing></outerBoundaryIs>
    <innerBoundaryIs><LinearRing><coordinates>
      -74.00,40.72 -73.98,40.72 -73.98,40.74 -74.00,40.74 -74.00,40.72
    </coordinates></LinearRing></innerBoundaryIs>
   </Polygon>
  </Placemark>
  <Placemark>
   <name>islands</name>
   <MultiGeometry>
    <Polygon><outerBoundaryIs><LinearRing><coordinates>
      0,0 1,0 1,1 0,1 0,0
    </coordinates></LinearRing></outerBoundaryIs></Polygon>
    <Polygon><outerBoundaryIs><LinearRing><coordinates>
      2,2 3,2 3,3 2,3 2,2
    </coordinates></LinearRing></outerBoundaryIs></Polygon>
   </MultiGeometry>
  </Placemark>
 </Document>
</kml>
"""


def test_kml_reader(tmp_path):
    from mosaic_tpu.core.types import GeometryType
    from mosaic_tpu.readers.registry import read

    p = tmp_path / "sample.kml"
    p.write_text(_KML_DOC)
    t = read("kml").load(str(p))
    assert len(t) == 4
    assert [t.geometry.geometry_type(g) for g in range(4)] == [
        GeometryType.POINT, GeometryType.LINESTRING,
        GeometryType.POLYGON, GeometryType.MULTIPOLYGON,
    ]
    assert t.columns["name"].tolist() == ["hq", "route", "zone", "islands"]
    assert t.columns["kind"][0] == "office"
    assert t.columns["code"][2] == "Z1"
    # point carries altitude as z, lon/lat order per spec
    np.testing.assert_allclose(t.geometry.geom_xy(0), [[-73.98, 40.75]])
    assert t.geometry.has_z(0)
    # holed polygon: area = outer - inner
    from mosaic_tpu import functions as F

    a = float(np.asarray(F.st_area(t.geometry.slice(2, 3)))[0])
    np.testing.assert_allclose(a, 0.06 * 0.06 - 0.02 * 0.02, atol=1e-12)
    # multipolygon: two parts, total area 2
    a2 = float(np.asarray(F.st_area(t.geometry.slice(3, 4)))[0])
    np.testing.assert_allclose(a2, 2.0, atol=1e-12)
    # srid is fixed to 4326 by the KML spec
    assert int(t.geometry.srid[2]) == 4326


def test_kml_mixed_multigeometry_uses_collection_rule(tmp_path):
    from mosaic_tpu.core.types import GeometryType
    from mosaic_tpu.readers.kml import read_kml

    doc = """<?xml version="1.0"?>
    <kml xmlns="http://www.opengis.net/kml/2.2"><Document><Placemark>
     <MultiGeometry>
      <Point><coordinates>5,5</coordinates></Point>
      <Polygon><outerBoundaryIs><LinearRing><coordinates>
        0,0 2,0 2,2 0,2 0,0
      </coordinates></LinearRing></outerBoundaryIs></Polygon>
     </MultiGeometry>
    </Placemark></Document></kml>"""
    p = tmp_path / "mixed.kml"
    p.write_text(doc)
    t = read_kml(p)
    # first-polygonal rule (shared with the WKT/WKB/GeoJSON codecs)
    assert t.geometry.geometry_type(0) == GeometryType.POLYGON
    assert t.geometry.geom_xy(0).shape[0] == 4


def test_kml_nested_mixed_multigeometry_and_sloppy_coords(tmp_path):
    # a nested MIXED MultiGeometry must not win the first-polygonal rule
    # over a real later Polygon; trailing commas must parse
    from mosaic_tpu.core.types import GeometryType
    from mosaic_tpu.readers.kml import read_kml

    doc = """<?xml version="1.0"?>
    <kml xmlns="http://www.opengis.net/kml/2.2"><Document><Placemark>
     <MultiGeometry>
      <MultiGeometry>
       <Point><coordinates>5,5,</coordinates></Point>
       <LineString><coordinates>0,0 1,1</coordinates></LineString>
      </MultiGeometry>
      <Polygon><outerBoundaryIs><LinearRing><coordinates>
        0,0 2,0 2,2 0,2 0,0
      </coordinates></LinearRing></outerBoundaryIs></Polygon>
     </MultiGeometry>
    </Placemark></Document></kml>"""
    p = tmp_path / "nested.kml"
    p.write_text(doc)
    t = read_kml(p)
    assert t.geometry.geometry_type(0) == GeometryType.POLYGON
    assert t.geometry.geom_xy(0).shape[0] == 4  # the real polygon won


# ----------------------------------------------------------- GML + GPX
_GML_DOC = """<?xml version="1.0" encoding="utf-8" ?>
<ogr:FeatureCollection xmlns:gml="http://www.opengis.net/gml"
                       xmlns:ogr="http://ogr.maptools.org/">
 <gml:featureMember>
  <ogr:zone>
   <ogr:name>alpha</ogr:name>
   <ogr:pop>120</ogr:pop>
   <ogr:geometryProperty>
    <gml:Polygon srsName="EPSG:4326">
     <gml:exterior><gml:LinearRing>
      <gml:posList>0 0 4 0 4 4 0 4 0 0</gml:posList>
     </gml:LinearRing></gml:exterior>
     <gml:interior><gml:LinearRing>
      <gml:posList>1 1 1 2 2 2 2 1 1 1</gml:posList>
     </gml:LinearRing></gml:interior>
    </gml:Polygon>
   </ogr:geometryProperty>
  </ogr:zone>
 </gml:featureMember>
 <gml:featureMember>
  <ogr:stop>
   <ogr:name>beta</ogr:name>
   <ogr:geometryProperty>
    <gml:Point><gml:pos>-73.98 40.75</gml:pos></gml:Point>
   </ogr:geometryProperty>
  </ogr:stop>
 </gml:featureMember>
 <gml:featureMember>
  <ogr:path>
   <ogr:geometryProperty>
    <gml:LineString>
     <gml:coordinates>0,0 1,1 2,0</gml:coordinates>
    </gml:LineString>
   </ogr:geometryProperty>
  </ogr:path>
 </gml:featureMember>
 <gml:featureMember>
  <ogr:lakes>
   <ogr:geometryProperty>
    <gml:MultiSurface>
     <gml:surfaceMember><gml:Polygon><gml:exterior><gml:LinearRing>
      <gml:posList>0 0 1 0 1 1 0 1 0 0</gml:posList>
     </gml:LinearRing></gml:exterior></gml:Polygon></gml:surfaceMember>
     <gml:surfaceMember><gml:Polygon><gml:exterior><gml:LinearRing>
      <gml:posList>3 3 4 3 4 4 3 4 3 3</gml:posList>
     </gml:LinearRing></gml:exterior></gml:Polygon></gml:surfaceMember>
    </gml:MultiSurface>
   </ogr:geometryProperty>
  </ogr:lakes>
 </gml:featureMember>
</ogr:FeatureCollection>
"""

_GPX_DOC = """<?xml version="1.0"?>
<gpx xmlns="http://www.topografix.com/GPX/1/1" version="1.1">
 <wpt lat="40.75" lon="-73.98"><ele>12.5</ele><name>hq</name></wpt>
 <rte><name>r1</name>
  <rtept lat="40.7" lon="-74.0"/><rtept lat="40.72" lon="-73.95"/>
 </rte>
 <trk><name>t1</name>
  <trkseg>
   <trkpt lat="40.60" lon="-74.05"/><trkpt lat="40.61" lon="-74.04"/>
   <trkpt lat="40.62" lon="-74.02"/>
  </trkseg>
 </trk>
</gpx>
"""


def test_gml_reader(tmp_path):
    from mosaic_tpu.core.types import GeometryType
    from mosaic_tpu.readers.registry import read
    from mosaic_tpu import functions as F

    p = tmp_path / "sample.gml"
    p.write_text(_GML_DOC)
    t = read("gml").load(str(p))
    assert len(t) == 4
    assert [t.geometry.geometry_type(g) for g in range(4)] == [
        GeometryType.POLYGON, GeometryType.POINT,
        GeometryType.LINESTRING, GeometryType.MULTIPOLYGON,
    ]
    assert t.columns["name"].tolist() == ["alpha", "beta", "", ""]
    assert t.columns["pop"][0] == "120"
    a = float(np.asarray(F.st_area(t.geometry.slice(0, 1)))[0])
    np.testing.assert_allclose(a, 16.0 - 1.0, atol=1e-12)
    a2 = float(np.asarray(F.st_area(t.geometry.slice(3, 4)))[0])
    np.testing.assert_allclose(a2, 2.0, atol=1e-12)
    np.testing.assert_allclose(t.geometry.geom_xy(1), [[-73.98, 40.75]])


def test_gpx_reader(tmp_path):
    from mosaic_tpu.core.types import GeometryType
    from mosaic_tpu.readers.vector import open_any

    p = tmp_path / "sample.gpx"
    p.write_text(_GPX_DOC)
    t = open_any(str(p))
    assert len(t) == 3
    assert [t.geometry.geometry_type(g) for g in range(3)] == [
        GeometryType.POINT, GeometryType.LINESTRING, GeometryType.LINESTRING,
    ]
    assert t.columns["kind"].tolist() == ["wpt", "rte", "trkseg"]
    assert t.columns["name"].tolist() == ["hq", "r1", "t1"]  # trk name rides its segments
    assert t.geometry.has_z(0)  # ele became z
    assert t.geometry.geom_xy(2).shape[0] == 3


def test_gml_edge_cases(tmp_path):
    # mixed MultiGeometry -> collection rule; 3D posList via srsDimension
    # on the Polygon; multi-segment Curve concatenation
    from mosaic_tpu.core.types import GeometryType
    from mosaic_tpu.readers.gml import read_gml

    doc = """<?xml version="1.0"?>
    <c xmlns:gml="http://www.opengis.net/gml">
     <gml:featureMember><f><geom>
      <gml:MultiGeometry>
       <gml:geometryMember><gml:Point><gml:pos>9 9</gml:pos></gml:Point></gml:geometryMember>
       <gml:geometryMember><gml:Polygon><gml:exterior><gml:LinearRing>
         <gml:posList>0 0 2 0 2 2 0 2 0 0</gml:posList>
       </gml:LinearRing></gml:exterior></gml:Polygon></gml:geometryMember>
      </gml:MultiGeometry>
     </geom></f></gml:featureMember>
     <gml:featureMember><f><geom>
      <gml:Polygon srsDimension="3"><gml:exterior><gml:LinearRing>
        <gml:posList>0 0 5 4 0 5 4 4 5 0 4 5 0 0 5</gml:posList>
      </gml:LinearRing></gml:exterior></gml:Polygon>
     </geom></f></gml:featureMember>
     <gml:featureMember><f><geom>
      <gml:Curve><gml:segments>
       <gml:LineStringSegment><gml:posList>0 0 1 1</gml:posList></gml:LineStringSegment>
       <gml:LineStringSegment><gml:posList>1 1 2 0</gml:posList></gml:LineStringSegment>
      </gml:segments></gml:Curve>
     </geom></f></gml:featureMember>
     <gml:featureMember><f><geom>
      <gml:MultiGeometry>
       <gml:geometryMember><gml:Point><gml:pos>1 1</gml:pos></gml:Point></gml:geometryMember>
       <gml:geometryMember><gml:Point><gml:pos>2 2</gml:pos></gml:Point></gml:geometryMember>
      </gml:MultiGeometry>
     </geom></f></gml:featureMember>
    </c>"""
    p = tmp_path / "edge.gml"
    p.write_text(doc)
    t = read_gml(p)
    assert len(t) == 4
    g = t.geometry
    # mixed members: first-polygonal rule keeps the polygon
    assert g.geometry_type(0) == GeometryType.POLYGON
    assert g.geom_xy(0).shape[0] == 4
    # 3D ring: 4 vertices (closing dropped), z preserved
    assert g.geometry_type(1) == GeometryType.POLYGON
    assert g.geom_xy(1).shape[0] == 4
    assert g.has_z(1)
    # multi-segment curve concatenated, joint vertex deduped
    np.testing.assert_allclose(g.geom_xy(2), [[0, 0], [1, 1], [2, 0]])
    # homogeneous point members collapse to MULTIPOINT
    assert g.geometry_type(3) == GeometryType.MULTIPOINT


def test_gml_3d_poslist_without_srsdimension(tmp_path):
    # real-world GML omits srsDimension on 3-D posLists; the reader must
    # infer dim=3 when the token count divides only by 3 (9 tokens here),
    # not silently reshape to (-1, 2)
    from mosaic_tpu.core.types import GeometryType
    from mosaic_tpu.readers.gml import read_gml

    doc = """<c xmlns:gml="http://www.opengis.net/gml">
     <gml:featureMember><f><geom>
      <gml:LineString><gml:posList>0 0 5 1 1 6 2 0 7</gml:posList></gml:LineString>
     </geom></f></gml:featureMember>
    </c>"""
    p = tmp_path / "nodim3d.gml"
    p.write_text(doc)
    t = read_gml(p)
    g = t.geometry
    assert g.geometry_type(0) == GeometryType.LINESTRING
    np.testing.assert_allclose(g.geom_xy(0), [[0, 0], [1, 1], [2, 0]])
    assert g.has_z(0)


def test_mif_reader(tmp_path):
    """MapInfo MIF/MID: points, lines, multi-section plines, and a holed
    region (MIF marks no holes — nesting is resolved by containment)."""
    from mosaic_tpu.core.types import GeometryType
    from mosaic_tpu.readers.registry import read

    mif = """VERSION 300
Charset "WindowsLatin1"
DELIMITER ","
COLUMNS 2
  name Char(20)
  val Decimal(10,2)
DATA
POINT 10 20
  SYMBOL (34,0,12)
LINE 0 0 5 5
PLINE 3
0 0
2 2
4 0
  PEN (1,2,0)
REGION 2
  5
0 0
10 0
10 10
0 10
0 0
  4
2 2
2 4
4 2
2 2
  BRUSH (2,16777215,16777215)
PLINE MULTIPLE 2
2
0 0
1 1
2
5 5
6 6
"""
    mid = '"zoneA",1.50\n"zoneB",2\n"zoneC",3\n"zoneD",4.25\n"zoneE",5\n'
    (tmp_path / "t.mif").write_text(mif)
    (tmp_path / "t.mid").write_text(mid)
    t = read("mapinfo").load(tmp_path / "t.mif")
    assert len(t) == 5
    g = t.geometry
    assert g.geometry_type(0) == GeometryType.POINT
    np.testing.assert_allclose(g.geom_xy(0), [[10, 20]])
    assert g.geometry_type(1) == GeometryType.LINESTRING
    assert g.geometry_type(2) == GeometryType.LINESTRING
    assert g.geom_xy(2).shape[0] == 3
    # region: outer shell + contained hole
    assert g.geometry_type(3) == GeometryType.POLYGON
    from mosaic_tpu import functions as F

    area = float(np.asarray(F.st_area(t.geometry.take([3])))[0])
    assert abs(area - (100.0 - 2.0)) < 1e-9  # hole area 2 removed
    assert g.geometry_type(4) == GeometryType.MULTILINESTRING
    assert t.columns["name"][3] == "zoneD"
    assert t.columns["val"][3] == 4.25


def test_dxf_reader(tmp_path):
    """DXF entities: POINT, LINE, closed LWPOLYLINE, POLYLINE+VERTEX,
    CIRCLE tessellation; layer attribute column."""
    from mosaic_tpu.core.types import GeometryType
    from mosaic_tpu.readers.registry import read

    def pairs(*kv):
        return "\n".join(str(x) for x in kv)

    doc = pairs(
        0, "SECTION", 2, "ENTITIES",
        0, "POINT", 8, "sites", 10, 3.0, 20, 4.0,
        0, "LINE", 8, "roads", 10, 0.0, 20, 0.0, 11, 5.0, 21, 5.0,
        0, "LWPOLYLINE", 8, "parcels", 70, 1,
        10, 0.0, 20, 0.0, 10, 4.0, 20, 0.0, 10, 4.0, 20, 3.0, 10, 0.0, 20, 3.0,
        0, "POLYLINE", 8, "paths", 70, 0,
        0, "VERTEX", 10, 0.0, 20, 0.0,
        0, "VERTEX", 10, 1.0, 20, 2.0,
        0, "VERTEX", 10, 2.0, 20, 0.0,
        0, "SEQEND",
        0, "CIRCLE", 8, "wells", 10, 10.0, 20, 10.0, 40, 2.0,
        0, "ENDSEC",
        0, "EOF",
    ) + "\n"
    p = tmp_path / "t.dxf"
    p.write_text(doc)
    t = read("dxf").load(p)
    assert len(t) == 5
    g = t.geometry
    assert g.geometry_type(0) == GeometryType.POINT
    assert g.geometry_type(1) == GeometryType.LINESTRING
    assert g.geometry_type(2) == GeometryType.POLYGON
    from mosaic_tpu import functions as F

    assert abs(float(np.asarray(F.st_area(g.take([2])))[0]) - 12.0) < 1e-9
    assert g.geometry_type(3) == GeometryType.LINESTRING
    assert g.geom_xy(3).shape[0] == 3
    assert g.geometry_type(4) == GeometryType.POLYGON
    circ = float(np.asarray(F.st_area(g.take([4])))[0])
    assert abs(circ - np.pi * 4.0) < 0.1  # 64-gon approximation
    assert list(t.columns["layer"]) == [
        "sites", "roads", "parcels", "paths", "wells"
    ]


def test_mif_skips_unsupported_objects_keeping_mid_alignment(tmp_path):
    """TEXT/RECT objects become empty rows (OGR-skip analog) so the .mid
    attribute rows stay aligned; a hole touching its shell still nests."""
    from mosaic_tpu.readers.registry import read

    mif = """VERSION 300
COLUMNS 1
  name Char(10)
DATA
POINT 1 2
TEXT
  "caption here"
  0 0 5 1
REGION 2
  5
0 0
8 0
8 8
0 8
0 0
  4
0 0
3 1
1 3
0 0
"""
    mid = '"a"\n"skip"\n"holed"\n'
    (tmp_path / "s.mif").write_text(mif)
    (tmp_path / "s.mid").write_text(mid)
    t = read("mif").load(tmp_path / "s.mif")
    assert len(t) == 3
    assert list(t.columns["name"]) == ["a", "skip", "holed"]
    from mosaic_tpu import functions as F

    # hole (area 4) shares vertex (0,0) with the shell — must still nest
    area = float(np.asarray(F.st_area(t.geometry.take([2])))[0])
    assert abs(area - (64.0 - 4.0)) < 1e-9


def test_mif_dxf_through_open_any(tmp_path):
    from mosaic_tpu.readers.vector import open_any

    (tmp_path / "p.mif").write_text("VERSION 300\nCOLUMNS 0\nDATA\nPOINT 7 8\n")
    assert len(open_any(tmp_path / "p.mif")) == 1
    (tmp_path / "p.dxf").write_text(
        "0\nSECTION\n2\nENTITIES\n0\nPOINT\n8\nL\n10\n1.0\n20\n2.0\n"
        "0\nENDSEC\n0\nEOF\n"
    )
    assert len(open_any(tmp_path / "p.dxf")) == 1


def _shp_record(recno: int, payload: bytes) -> bytes:
    import struct

    return struct.pack(">ii", recno, len(payload) // 2) + payload


def test_shapefile_all_shape_types_and_dbf_typing(tmp_path):
    """Hand-built .shp exercising NULL/POINT/MULTIPOINT/POLYLINE/POLYGON
    records plus .dbf C/N/F/L typing and the .prj srid sniff."""
    import struct

    from mosaic_tpu.core.types import GeometryType
    from mosaic_tpu.readers.vector import read_shapefile

    recs = []
    # null shape
    recs.append(_shp_record(1, struct.pack("<i", 0)))
    # point
    recs.append(_shp_record(2, struct.pack("<idd", 1, 3.0, 4.0)))
    # multipoint: bbox + count + 2 points
    mp = struct.pack("<i4di", 8, 0, 0, 2, 2, 2) + struct.pack(
        "<4d", 0.0, 0.0, 2.0, 2.0
    )
    recs.append(_shp_record(3, mp))
    # polyline, two parts
    pl = (
        struct.pack("<i4dii", 3, 0, 0, 5, 5, 2, 4)
        + struct.pack("<2i", 0, 2)
        + struct.pack("<8d", 0, 0, 1, 1, 2, 2, 3, 1)
    )
    recs.append(_shp_record(4, pl))
    # polygon: CW shell + CCW hole (closed rings)
    shell = [(0, 0), (0, 8), (8, 8), (8, 0), (0, 0)]  # CW (area<0 shoelace)
    hole = [(2, 2), (4, 2), (4, 4), (2, 4), (2, 2)]  # CCW
    pts = shell + hole
    pg = (
        struct.pack("<i4dii", 5, 0, 0, 8, 8, 2, len(pts))
        + struct.pack("<2i", 0, len(shell))
        + b"".join(struct.pack("<2d", x, y) for x, y in pts)
    )
    recs.append(_shp_record(5, pg))
    body = b"".join(recs)
    hdr = struct.pack(">i", 9994) + b"\0" * 20 + struct.pack(
        ">i", (100 + len(body)) // 2
    ) + struct.pack("<ii", 1000, 0) + struct.pack("<8d", 0, 0, 8, 8, 0, 0, 0, 0)
    (tmp_path / "t.shp").write_bytes(hdr + body)

    # dbf: name C(6), n N(6,0), f F(8,2), flag L(1)
    def field(name, ftype, flen, fdec):
        return name.ljust(11, "\0").encode() + ftype.encode() + b"\0" * 4 + bytes(
            [flen, fdec]
        ) + b"\0" * 14

    fields = field("name", "C", 6, 0) + field("n", "N", 6, 0) + field(
        "f", "F", 8, 2
    ) + field("flag", "L", 1, 0)
    rec_len = 1 + 6 + 6 + 8 + 1
    rows = b""
    for k in range(5):
        rows += b" " + f"r{k}".ljust(6).encode() + str(k).rjust(6).encode() + (
            f"{k + 0.5:8.2f}".encode()
        ) + (b"T" if k % 2 else b"F")
    hdr_len = 32 + 4 * 32 + 1
    dbf = (
        bytes([3, 126, 1, 1])
        + struct.pack("<IHH", 5, hdr_len, rec_len)
        + b"\0" * 20
        + fields
        + b"\x0d"
        + rows
    )
    (tmp_path / "t.dbf").write_bytes(dbf)
    (tmp_path / "t.prj").write_text('PROJCS["OSGB 1936 / British National Grid"]')

    t = read_shapefile(str(tmp_path / "t.shp"))
    g = t.geometry
    assert len(t) == 5
    assert g.geometry_type(1) == GeometryType.POINT
    assert g.geometry_type(2) == GeometryType.MULTIPOINT
    assert g.geometry_type(3) == GeometryType.MULTILINESTRING
    assert g.geometry_type(4) == GeometryType.POLYGON
    assert (np.asarray(g.srid) == 27700).all()  # .prj sniffed
    from mosaic_tpu import functions as F

    area = float(np.asarray(F.st_area(g.take([4])))[0])
    assert abs(area - (64.0 - 4.0)) < 1e-9  # hole subtracted
    assert t.columns["n"].dtype == np.int64 and t.columns["n"][3] == 3
    assert t.columns["f"].dtype == np.float64 and t.columns["f"][2] == 2.5
    assert t.columns["flag"].dtype == bool and list(t.columns["flag"][:2]) == [
        False, True,
    ]
    assert t.columns["name"][0] == "r0"


# ---------------------------------------------------------------- TopoJSON
def test_topojson_quantized_shared_arc(tmp_path):
    """Two unit squares sharing a delta-encoded arc; the right square
    traverses it reversed (~0). Decoded areas and the junction-point
    dedup are asserted against hand-computed coordinates."""
    import json

    from mosaic_tpu import functions as F
    from mosaic_tpu.core.types import GeometryType
    from mosaic_tpu.readers.registry import read

    topo = {
        "type": "Topology",
        "transform": {"scale": [0.001, 0.001], "translate": [0.0, 0.0]},
        "arcs": [
            [[1000, 0], [0, 1000]],                                # shared
            [[1000, 1000], [-1000, 0], [0, -1000], [1000, 0]],     # left
            [[1000, 0], [1000, 0], [0, 1000], [-1000, 0]],         # right
        ],
        "objects": {
            "squares": {
                "type": "GeometryCollection",
                "geometries": [
                    {"type": "Polygon", "arcs": [[0, 1]],
                     "properties": {"name": "L"}},
                    {"type": "Polygon", "arcs": [[2, -1]],
                     "properties": {"name": "R"}},
                ],
            },
            "site": {"type": "Point", "coordinates": [500, 500],
                     "properties": {"name": "P"}},
        },
    }
    p = tmp_path / "t.topojson"
    p.write_text(json.dumps(topo))
    t = read("topojson").load(str(p))
    assert len(t) == 3
    g = t.geometry
    assert g.geometry_type(0) == GeometryType.POLYGON
    # left ring: stitched (1,0),(1,1),(0,1),(0,0) — junction appears once
    np.testing.assert_allclose(
        g.geom_xy(0), [[1, 0], [1, 1], [0, 1], [0, 0]], atol=1e-12
    )
    areas = np.asarray(F.st_area(g))
    np.testing.assert_allclose(areas[:2], [1.0, 1.0], atol=1e-12)
    # quantized Point positions are absolute, not deltas
    assert g.geometry_type(2) == GeometryType.POINT
    np.testing.assert_allclose(g.geom_xy(2), [[0.5, 0.5]], atol=1e-12)
    assert list(t.columns["layer"]) == ["squares", "squares", "site"]
    assert list(t.columns["name"]) == ["L", "R", "P"]
    # layer selection mirrors OGR's per-object layers
    only = read("topojson").option("layer", "site").load(str(p))
    assert len(only) == 1 and only.columns["layer"][0] == "site"
    with pytest.raises(ValueError, match="no such TopoJSON object"):
        read("topojson").option("layer", "nope").load(str(p))


def test_topojson_unquantized_hole_line_and_open_any(tmp_path):
    """No transform: arc positions are absolute floats (no cumsum). A
    holed polygon and a two-arc line round-trip; open_any dispatches on
    the .topojson suffix."""
    import json

    from mosaic_tpu import functions as F
    from mosaic_tpu.core.types import GeometryType
    from mosaic_tpu.readers.vector import open_any

    topo = {
        "type": "Topology",
        "arcs": [
            [[0.0, 0.0], [4.0, 0.0], [4.0, 4.0], [0.0, 4.0], [0.0, 0.0]],
            [[1.0, 1.0], [1.0, 2.0], [2.0, 2.0], [2.0, 1.0], [1.0, 1.0]],
            [[0.0, 0.0], [1.0, 1.0]],
            [[1.0, 1.0], [3.0, 1.0]],
        ],
        "objects": {
            "poly": {"type": "Polygon", "arcs": [[0], [1]]},
            "path": {"type": "LineString", "arcs": [2, 3]},
        },
    }
    p = tmp_path / "h.topojson"
    p.write_text(json.dumps(topo))
    t = open_any(str(p))
    assert len(t) == 2
    area = float(np.asarray(F.st_area(t.geometry.take([0])))[0])
    assert abs(area - (16.0 - 1.0)) < 1e-12
    assert t.geometry.geometry_type(1) == GeometryType.LINESTRING
    np.testing.assert_allclose(
        t.geometry.geom_xy(1), [[0, 0], [1, 1], [3, 1]], atol=1e-12
    )


def test_csv_wkt_reader(tmp_path):
    """OGR CSV-driver analog: a WKT geometry column plus attributes."""
    from mosaic_tpu import functions as F
    from mosaic_tpu.core.types import GeometryType
    from mosaic_tpu.readers.registry import read

    p = tmp_path / "t.csv"
    p.write_text(
        'id,wkt,score\n'
        '1,"POINT (3 4)",0.5\n'
        '2,"POLYGON ((0 0, 2 0, 2 2, 0 2, 0 0))",1.5\n'
        '3,"LINESTRING (0 0, 1 1)",2.5\n'
    )
    t = read("csv_wkt").load(str(p))
    assert len(t) == 3
    g = t.geometry
    assert g.geometry_type(0) == GeometryType.POINT
    assert g.geometry_type(1) == GeometryType.POLYGON
    assert float(np.asarray(F.st_area(g.take([1])))[0]) == 4.0
    assert int(g.srid[0]) == 4326
    assert list(t.columns["id"]) == ["1", "2", "3"]
    assert list(t.columns["score"]) == ["0.5", "1.5", "2.5"]
    with pytest.raises(ValueError, match="no column"):
        read("csv_wkt").option("wktCol", "geom").load(str(p))


# -------------------------------------------------------------- FlatGeobuf
def test_flatgeobuf_roundtrip_all_types(tmp_path):
    """Writer->reader round-trip across every geometry type, with typed
    attribute columns. Both ends hand-speak the flatbuffers wire format;
    coordinates must survive bit-exactly (f64 end to end)."""
    from mosaic_tpu.functions.formats import st_astext
    from mosaic_tpu.core.geometry import wkt as W
    from mosaic_tpu.readers.flatgeobuf import read_flatgeobuf, write_flatgeobuf
    from mosaic_tpu.readers.registry import read
    from mosaic_tpu.readers.vector import VectorTable

    wkts = [
        "POINT (3 4)",
        "LINESTRING (0 0, 1 1, 2 0)",
        "POLYGON ((0 0, 4 0, 4 4, 0 4, 0 0), (1 1, 1 2, 2 2, 2 1, 1 1))",
        "MULTIPOINT ((0 0), (1 2))",
        "MULTILINESTRING ((0 0, 1 1), (2 2, 3 3, 4 2))",
        "MULTIPOLYGON (((0 0, 2 0, 2 2, 0 2, 0 0)), "
        "((5 5, 7 5, 7 7, 5 7, 5 5), (5.5 5.5, 5.5 6, 6 6, 6 5.5, 5.5 5.5)))",
    ]
    cols = {
        "name": np.asarray([f"f{i}" for i in range(len(wkts))], dtype=object),
        "score": np.asarray([0.5 * i for i in range(len(wkts))]),
    }
    p = str(tmp_path / "t.fgb")
    write_flatgeobuf(p, VectorTable(geometry=W.from_wkt(wkts), columns=cols))
    r = read_flatgeobuf(p)
    assert len(r) == len(wkts)

    def norm(s):
        return s.replace(", ", ",")

    for want, got in zip(wkts, st_astext(r.geometry)):
        assert norm(want) == norm(got)
    assert list(r.columns["name"]) == [f"f{i}" for i in range(len(wkts))]
    np.testing.assert_allclose(r.columns["score"], cols["score"])
    assert r.geometry.srid[0] == 4326
    # registry + suffix dispatch
    from mosaic_tpu.readers.vector import open_any

    assert len(read("flatgeobuf").load(p)) == len(wkts)
    assert len(open_any(p)) == len(wkts)


def test_flatgeobuf_coordinates_bit_exact(tmp_path):
    """Irrational coordinates survive the f64 vectors bit for bit."""
    from mosaic_tpu.core.types import GeometryBuilder, GeometryType
    from mosaic_tpu.readers.flatgeobuf import read_flatgeobuf, write_flatgeobuf
    from mosaic_tpu.readers.vector import VectorTable

    rng = np.random.default_rng(42)
    xy = rng.uniform(-180, 180, (7, 2))
    b = GeometryBuilder()
    b.add_ring(xy)
    b.end_part()
    b.end_geom(GeometryType.LINESTRING, 4326)
    p = str(tmp_path / "bits.fgb")
    write_flatgeobuf(p, VectorTable(geometry=b.build(), columns={}))
    r = read_flatgeobuf(p)
    got = r.geometry.geom_xy(0)
    assert (got == xy).all()  # bit-exact, no tolerance


def test_flatgeobuf_header_and_errors(tmp_path):
    from mosaic_tpu.readers.flatgeobuf import (
        _index_bytes,
        read_flatgeobuf,
        write_flatgeobuf,
    )

    # packed-R-tree size recurrence (spec): 100 leaves at node 16 ->
    # 100 + 7 + 1 nodes of 40 bytes
    assert _index_bytes(100, 16) == 108 * 40
    assert _index_bytes(0, 16) == 0
    assert _index_bytes(5, 0) == 0  # no index
    bad = tmp_path / "bad.fgb"
    bad.write_bytes(b"nonsense")
    with pytest.raises(ValueError, match="not a FlatGeobuf"):
        read_flatgeobuf(str(bad))
    # truncated feature count: header promises more features than present
    from mosaic_tpu.core.geometry import wkt as W
    from mosaic_tpu.readers.vector import VectorTable

    p = str(tmp_path / "t.fgb")
    write_flatgeobuf(p, VectorTable(
        geometry=W.from_wkt(["POINT (1 2)"] * 3), columns={}
    ))
    whole = open(p, "rb").read()
    # chop the last feature frame off
    import struct as _s

    cut = whole
    # walk frames to find the final feature start
    q = 8
    (hl,) = _s.unpack_from("<I", cut, q)
    q += 4 + hl
    starts = []
    while q < len(cut):
        starts.append(q)
        (fl,) = _s.unpack_from("<I", cut, q)
        q += 4 + fl
    open(p, "wb").write(cut[: starts[-1]])
    with pytest.raises(ValueError, match="promises 3 features"):
        read_flatgeobuf(p)


def test_flatgeobuf_null_geometry_and_trailing_bytes(tmp_path):
    """Empty collections (the null-geometry marker) round-trip as
    null-geometry features; trailing bytes after the promised feature
    count are ignored, but a truncated frame errors loudly."""
    from mosaic_tpu.core.geometry import wkt as W
    from mosaic_tpu.core.types import GeometryType
    from mosaic_tpu.readers.flatgeobuf import read_flatgeobuf, write_flatgeobuf
    from mosaic_tpu.readers.vector import VectorTable

    wkts = ["POINT (1 2)", "GEOMETRYCOLLECTION EMPTY", "POINT (3 4)"]
    p = str(tmp_path / "n.fgb")
    write_flatgeobuf(p, VectorTable(geometry=W.from_wkt(wkts), columns={}))
    r = read_flatgeobuf(p)
    assert len(r) == 3
    assert r.geometry.geometry_type(1) == GeometryType.GEOMETRYCOLLECTION
    np.testing.assert_allclose(r.geometry.geom_xy(2), [[3, 4]])
    # trailing garbage after the promised count is not a frame
    with open(p, "ab") as f:
        f.write(b"\x00\x01\x02\x03\x04\x05")
    assert len(read_flatgeobuf(p)) == 3
    # a frame length overrunning the file is a loud error
    whole = open(p, "rb").read()
    open(p, "wb").write(whole[:-10])
    with pytest.raises(ValueError):
        read_flatgeobuf(p)


def test_flatgeobuf_z_roundtrip(tmp_path):
    """3D geometries keep their Z through write->read (header has_z flag
    + slot-2 z vectors, closed in step with polygon rings)."""
    from mosaic_tpu.core.geometry import wkt as W
    from mosaic_tpu.readers.flatgeobuf import read_flatgeobuf, write_flatgeobuf
    from mosaic_tpu.readers.vector import VectorTable

    wkts = [
        "POINT Z (1 2 7)",
        "LINESTRING Z (0 0 1, 1 1 2, 2 0 3)",
        "POLYGON Z ((0 0 5, 4 0 6, 4 4 7, 0 4 8, 0 0 5))",
    ]
    p = str(tmp_path / "z.fgb")
    write_flatgeobuf(p, VectorTable(geometry=W.from_wkt(wkts), columns={}))
    r = read_flatgeobuf(p)
    g = r.geometry
    assert all(g.has_z(i) for i in range(3))
    np.testing.assert_allclose(g.ring_z(0), [7.0])
    np.testing.assert_allclose(g.ring_z(1), [1.0, 2.0, 3.0])
    np.testing.assert_allclose(g.ring_z(2), [5.0, 6.0, 7.0, 8.0])
    # 2D rows written alongside 3D stay 2D (per-geometry z emission)
    p2 = str(tmp_path / "mix.fgb")
    write_flatgeobuf(p2, VectorTable(
        geometry=W.from_wkt(["POINT Z (1 2 7)", "POINT (3 4)"]), columns={}
    ))
    r2 = read_flatgeobuf(p2)
    assert r2.geometry.has_z(0) and not r2.geometry.has_z(1)


def test_write_shapefile_round_trip(tmp_path):
    """write_shapefile -> read_shapefile: geometry, typed DBF columns
    (N/C/L), NULL shapes for empties, ring orientation (shp CW shells)."""
    import numpy as np

    from mosaic_tpu.core.geometry import wkt
    from mosaic_tpu.readers.vector import (
        VectorTable,
        read_shapefile,
        write_shapefile,
    )

    col = wkt.from_wkt([
        "POLYGON ((0 0, 10 0, 10 10, 0 10, 0 0), (2 2, 2 4, 4 4, 4 2, 2 2))",
        "MULTIPOLYGON (((20 0, 30 0, 25 9, 20 0)), ((40 0, 50 0, 45 9, 40 0)))",
        "POLYGON EMPTY",
    ])
    t = VectorTable(
        geometry=col,
        columns={
            "name": np.asarray(["a", "b", "c"], object),
            "v": np.asarray([1.25, -2.5, 3.0]),
            "n": np.asarray([7, 8, 9], np.int64),
            "f": np.asarray([True, False, True]),
        },
    )
    p = tmp_path / "zones.shp"
    write_shapefile(str(p), t)
    r = read_shapefile(str(p))
    assert len(r) == 3
    assert list(r.columns["name"]) == ["a", "b", "c"]
    np.testing.assert_allclose(r.columns["v"], t.columns["v"])
    np.testing.assert_array_equal(r.columns["n"], t.columns["n"])
    np.testing.assert_array_equal(r.columns["f"], t.columns["f"])
    from mosaic_tpu.core.geometry import oracle

    # same containment behavior after the round trip (vertex order may
    # rotate; the polygon must not change)
    pts = np.asarray([[5.0, 5.0], [3.0, 3.0], [25.0, 3.0], [45.0, 3.0]])
    for g in range(2):
        np.testing.assert_array_equal(
            oracle.contains_points(r.geometry, g, pts),
            oracle.contains_points(col, g, pts),
        )
    assert r.geometry.geom_xy(2).shape[0] == 0


def test_write_geojson_seq_round_trip(tmp_path):
    import numpy as np

    from mosaic_tpu.core.geometry import wkt
    from mosaic_tpu.readers import read, write_geojson
    from mosaic_tpu.readers.vector import VectorTable

    col = wkt.from_wkt(["POINT (1 2)", "LINESTRING (0 0, 2 3)"])
    t = VectorTable(
        geometry=col, columns={"v": np.asarray([np.nan, 2.0])}
    )
    p = tmp_path / "x.geojsonl"
    write_geojson(str(p), t, seq=True)
    r = read("geojsonseq").load(str(p))
    assert len(r) == 2 and np.isnan(r.columns["v"][0])
    assert "LINESTRING" in wkt.to_wkt(r.geometry)[1]


def test_write_registry_round_trips(tmp_path):
    """write(fmt).save -> read(fmt).load across every registered writer."""
    import numpy as np

    from mosaic_tpu.core.geometry import wkt
    from mosaic_tpu.readers import read, write
    from mosaic_tpu.readers.vector import VectorTable

    col = wkt.from_wkt(
        ["POLYGON ((0 0, 4 0, 4 4, 0 4, 0 0))", "POLYGON ((5 5, 9 5, 9 9, 5 9, 5 5))"]
    )
    t = VectorTable(
        geometry=col,
        columns={"v": np.asarray([1.5, 2.5])},
    )
    cases = {
        "geojson": "a.geojson",
        "geojsonseq": "a.geojsonl",
        "shapefile": "a.shp",
        "flatgeobuf": "a.fgb",
        "geopackage": "a.gpkg",
    }
    for fmt, name in cases.items():
        p = str(tmp_path / name)
        write(fmt).save(p, t)
        r = read(fmt).load(p)
        assert len(r) == 2, fmt
        np.testing.assert_allclose(
            np.sort(np.asarray(r.columns["v"], float)), [1.5, 2.5],
            err_msg=fmt,
        )
        ws = " ".join(wkt.to_wkt(r.geometry))
        assert ws.count("POLYGON") == 2, (fmt, ws)


def test_osm_reader(tmp_path):
    """OSM XML: tagged nodes -> points, closed area-tagged ways ->
    polygons, highways stay lines, multipolygon relations chain their
    member ways into rings (reference: the OGR OSM driver behind
    OGRFileFormat.scala:26-47)."""
    import numpy as np

    from mosaic_tpu.core.geometry import wkt
    from mosaic_tpu.readers import read

    osm = """<?xml version='1.0'?>
<osm version="0.6">
 <node id="1" lat="40.0" lon="-74.0"><tag k="amenity" v="cafe"/></node>
 <node id="2" lat="40.001" lon="-74.0"/>
 <node id="3" lat="40.001" lon="-73.999"/>
 <node id="4" lat="40.0" lon="-73.999"/>
 <node id="5" lat="40.0" lon="-74.0"/>
 <way id="100"><nd ref="5"/><nd ref="2"/><nd ref="3"/><nd ref="4"/>
   <nd ref="5"/><tag k="building" v="yes"/></way>
 <way id="101"><nd ref="2"/><nd ref="3"/>
   <tag k="highway" v="residential"/></way>
 <way id="200"><nd ref="5"/><nd ref="2"/><nd ref="3"/></way>
 <way id="201"><nd ref="3"/><nd ref="4"/><nd ref="5"/></way>
 <relation id="300"><tag k="type" v="multipolygon"/>
   <member type="way" ref="200" role="outer"/>
   <member type="way" ref="201" role="outer"/></relation>
</osm>"""
    p = tmp_path / "x.osm"
    p.write_text(osm)
    t = read("osm").load(str(p))
    kinds = list(t.columns["kind"])
    assert kinds == ["point", "polygon", "line", "multipolygon"]
    assert list(t.columns["osm_id"]) == [1, 100, 101, 300]
    w = wkt.to_wkt(t.geometry)
    assert w[0].startswith("POINT") and w[1].startswith("POLYGON")
    from mosaic_tpu.core.geometry import oracle

    # the relation's chained rings enclose the same square as way 100
    inside = oracle.contains_points(
        t.geometry, 3, np.asarray([[-73.9995, 40.0005]])
    )
    assert bool(inside[0])


def test_write_kml_round_trip(tmp_path):
    import numpy as np

    from mosaic_tpu.core.geometry import wkt
    from mosaic_tpu.readers import read, write
    from mosaic_tpu.readers.vector import VectorTable

    col = wkt.from_wkt([
        "POLYGON ((0 0, 5 0, 5 5, 0 5, 0 0), (1 1, 1 2, 2 2, 2 1, 1 1))",
        "MULTIPOLYGON (((10 0, 12 0, 11 2, 10 0)), ((20 0, 22 0, 21 2, 20 0)))",
        "LINESTRING (0 0, 2 3)",
    ])
    t = VectorTable(
        geometry=col,
        columns={
            "nm": np.asarray(["a", "b", "c"], object),
            "v": np.asarray([1.5, 2.5, 3.5]),
        },
    )
    p = str(tmp_path / "x.kml")
    write("kml").option("name_col", "nm").save(p, t)
    r = read("kml").load(p)
    assert len(r) == 3
    w = wkt.to_wkt(r.geometry)
    assert w[0].startswith("POLYGON") and "1 1" in w[0]  # hole survives
    assert w[1].startswith("MULTIPOLYGON")
    assert list(r.columns["name"]) == ["a", "b", "c"]
    np.testing.assert_allclose(
        np.asarray(r.columns["v"], float), [1.5, 2.5, 3.5]
    )


def test_osm_closed_waterway_and_place_are_polygons(tmp_path):
    """`waterway` and `place` are SEPARATE area keys: the seed's missing
    comma concatenated them into one bogus "waterwayplace" key, so a
    closed riverbank way came back as a line."""
    from mosaic_tpu.readers import read

    osm = """<?xml version='1.0'?>
<osm version="0.6">
 <node id="1" lat="40.0" lon="-74.0"/>
 <node id="2" lat="40.001" lon="-74.0"/>
 <node id="3" lat="40.001" lon="-73.999"/>
 <node id="4" lat="40.0" lon="-73.999"/>
 <way id="10"><nd ref="1"/><nd ref="2"/><nd ref="3"/><nd ref="4"/>
   <nd ref="1"/><tag k="waterway" v="riverbank"/></way>
 <way id="11"><nd ref="1"/><nd ref="2"/><nd ref="3"/><nd ref="4"/>
   <nd ref="1"/><tag k="place" v="island"/></way>
</osm>"""
    p = tmp_path / "water.osm"
    p.write_text(osm)
    t = read("osm").load(str(p))
    assert list(t.columns["kind"]) == ["polygon", "polygon"]


def test_write_kml_quoted_attribute_round_trip(tmp_path):
    """Column names land in Data name="..." attributes: quotes must be
    escaped quoteattr-style or the attribute terminates early."""
    import numpy as np

    from mosaic_tpu.core.geometry import wkt
    from mosaic_tpu.readers.kml import read_kml, write_kml
    from mosaic_tpu.readers.vector import VectorTable

    col = wkt.from_wkt(["POINT (1 2)", "POINT (3 4)"])
    quoted = 'he said "hi" & <ok>\'s'
    t = VectorTable(
        geometry=col,
        columns={quoted: np.asarray(["a\"b", "c'd"], object)},
    )
    p = str(tmp_path / "q.kml")
    write_kml(p, t)
    r = read_kml(p)
    assert quoted in r.columns
    assert list(r.columns[quoted]) == ['a"b', "c'd"]
