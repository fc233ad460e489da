"""Synthetic TPC-H data generator + the 22 query texts.

The analogue of the reference's TPC-DS-style q1-q99 suite
(tests/unit/test_queries.py there) — the coverage yardstick for the engine.
Data is random but schema-faithful, tiny by default (scale via n_*).
"""
from __future__ import annotations

import numpy as np
import pandas as pd

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1), ("EGYPT", 4),
    ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3), ("INDIA", 2), ("INDONESIA", 2),
    ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0),
    ("MOROCCO", 0), ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1),
]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
MODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
INSTRUCTS = ["COLLECT COD", "DELIVER IN PERSON", "NONE", "TAKE BACK RETURN"]
TYPES = [f"{a} {b} {c}" for a in ("ECONOMY", "LARGE", "MEDIUM", "PROMO", "SMALL", "STANDARD")
         for b in ("ANODIZED", "BRUSHED", "BURNISHED", "PLATED", "POLISHED")
         for c in ("BRASS", "COPPER", "NICKEL", "STEEL", "TIN")]
CONTAINERS = [f"{a} {b}" for a in ("JUMBO", "LG", "MED", "SM", "WRAP")
              for b in ("BAG", "BOX", "CAN", "CASE", "DRUM", "JAR", "PACK", "PKG")]


def _dates(rng, n, start="1992-01-01", days=2526):
    base = np.datetime64(start)
    return base + rng.randint(0, days, n).astype("timedelta64[D]")


def _sparse_keys(n):
    """Order keys as clause 4.2.3 populates them: the first 8 of every 32.
    Dense keys hid that a LUT sized by the FILTERED build's rows declines."""
    index = np.arange(n, dtype=np.int64)
    return (index // 8) * 32 + index % 8 + 1


def generate(scale_rows: int = 2000, seed: int = 7):
    """All 8 TPC-H tables; `scale_rows` ~ lineitem row count."""
    rng = np.random.RandomState(seed)
    n_li = scale_rows
    n_ord = max(scale_rows // 4, 10)
    n_cust = max(scale_rows // 10, 10)
    n_part = max(scale_rows // 10, 10)
    n_supp = max(scale_rows // 100, 5)

    region = pd.DataFrame({
        "r_regionkey": np.arange(5, dtype=np.int64),
        "r_name": REGIONS,
        "r_comment": ["" for _ in REGIONS],
    })
    nation = pd.DataFrame({
        "n_nationkey": np.arange(len(NATIONS), dtype=np.int64),
        "n_name": [n for n, _ in NATIONS],
        "n_regionkey": np.array([r for _, r in NATIONS], dtype=np.int64),
        "n_comment": ["" for _ in NATIONS],
    })
    supplier = pd.DataFrame({
        "s_suppkey": np.arange(1, n_supp + 1, dtype=np.int64),
        "s_name": [f"Supplier#{i:09d}" for i in range(1, n_supp + 1)],
        "s_address": [f"addr{i}" for i in range(n_supp)],
        "s_nationkey": rng.randint(0, len(NATIONS), n_supp).astype(np.int64),
        "s_phone": [f"{rng.randint(10, 35)}-{i:03d}" for i in range(n_supp)],
        "s_acctbal": np.round(rng.rand(n_supp) * 11000 - 1000, 2),
        "s_comment": ["Customer Complaints" if rng.rand() < 0.05 else "fine" for _ in range(n_supp)],
    })
    part = pd.DataFrame({
        "p_partkey": np.arange(1, n_part + 1, dtype=np.int64),
        "p_name": [f"{rng.choice(['green','blue','red','ivory','forest'])} part {i}" for i in range(n_part)],
        "p_mfgr": [f"Manufacturer#{rng.randint(1, 6)}" for _ in range(n_part)],
        "p_brand": [f"Brand#{rng.randint(1, 6)}{rng.randint(1, 6)}" for _ in range(n_part)],
        "p_type": rng.choice(TYPES, n_part),
        "p_size": rng.randint(1, 51, n_part).astype(np.int64),
        "p_container": rng.choice(CONTAINERS, n_part),
        "p_retailprice": np.round(900 + rng.rand(n_part) * 1200, 2),
        "p_comment": ["" for _ in range(n_part)],
    })
    partsupp_rows = []
    for pk in range(1, n_part + 1):
        for s in rng.choice(np.arange(1, n_supp + 1), size=min(2, n_supp), replace=False):
            partsupp_rows.append((pk, int(s)))
    partsupp = pd.DataFrame(partsupp_rows, columns=["ps_partkey", "ps_suppkey"])
    partsupp["ps_availqty"] = rng.randint(1, 10000, len(partsupp)).astype(np.int64)
    partsupp["ps_supplycost"] = np.round(1 + rng.rand(len(partsupp)) * 1000, 2)
    partsupp["ps_comment"] = ""

    customer = pd.DataFrame({
        "c_custkey": np.arange(1, n_cust + 1, dtype=np.int64),
        "c_name": [f"Customer#{i:09d}" for i in range(1, n_cust + 1)],
        "c_address": [f"caddr{i}" for i in range(n_cust)],
        "c_nationkey": rng.randint(0, len(NATIONS), n_cust).astype(np.int64),
        "c_phone": [f"{rng.randint(10, 35)}-{i:04d}" for i in range(n_cust)],
        "c_acctbal": np.round(rng.rand(n_cust) * 11000 - 1000, 2),
        "c_mktsegment": rng.choice(SEGMENTS, n_cust),
        "c_comment": ["" for _ in range(n_cust)],
    })
    orders = pd.DataFrame({
        "o_orderkey": _sparse_keys(n_ord),
        "o_custkey": rng.randint(1, n_cust + 1, n_ord).astype(np.int64),
        "o_orderstatus": rng.choice(["F", "O", "P"], n_ord),
        "o_totalprice": np.round(1000 + rng.rand(n_ord) * 400000, 2),
        "o_orderdate": _dates(rng, n_ord),
        "o_orderpriority": rng.choice(PRIORITIES, n_ord),
        "o_clerk": [f"Clerk#{rng.randint(1, 100):09d}" for _ in range(n_ord)],
        "o_shippriority": np.zeros(n_ord, dtype=np.int64),
        "o_comment": rng.choice(["", "special requests", "deposits"], n_ord),
    })
    okeys = orders.o_orderkey.to_numpy()[rng.randint(1, n_ord + 1, n_li) - 1]
    odate_by_key = orders.set_index("o_orderkey").o_orderdate
    shipbase = odate_by_key.loc[okeys].to_numpy()
    lineitem = pd.DataFrame({
        "l_orderkey": okeys,
        "l_partkey": rng.randint(1, n_part + 1, n_li).astype(np.int64),
        "l_suppkey": rng.randint(1, n_supp + 1, n_li).astype(np.int64),
        "l_linenumber": (np.arange(n_li) % 7 + 1).astype(np.int64),
        "l_quantity": rng.randint(1, 51, n_li).astype(np.float64),
        "l_extendedprice": np.round(rng.rand(n_li) * 100000, 2),
        "l_discount": np.round(rng.randint(0, 11, n_li) / 100.0, 2),
        "l_tax": np.round(rng.randint(0, 9, n_li) / 100.0, 2),
        "l_returnflag": rng.choice(["A", "N", "R"], n_li),
        "l_linestatus": rng.choice(["F", "O"], n_li),
        "l_shipdate": shipbase + rng.randint(1, 122, n_li).astype("timedelta64[D]"),
        "l_commitdate": shipbase + rng.randint(30, 91, n_li).astype("timedelta64[D]"),
        "l_receiptdate": shipbase + rng.randint(1, 153, n_li).astype("timedelta64[D]"),
        "l_shipinstruct": rng.choice(INSTRUCTS, n_li),
        "l_shipmode": rng.choice(MODES, n_li),
        "l_comment": ["" for _ in range(n_li)],
    })
    return {
        "region": region, "nation": nation, "supplier": supplier, "part": part,
        "partsupp": partsupp, "customer": customer, "orders": orders,
        "lineitem": lineitem,
    }


QUERIES = {
    1: """
        SELECT l_returnflag, l_linestatus,
               SUM(l_quantity) AS sum_qty,
               SUM(l_extendedprice) AS sum_base_price,
               SUM(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
               SUM(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
               AVG(l_quantity) AS avg_qty,
               AVG(l_extendedprice) AS avg_price,
               AVG(l_discount) AS avg_disc,
               COUNT(*) AS count_order
        FROM lineitem
        WHERE l_shipdate <= DATE '1998-09-02'
        GROUP BY l_returnflag, l_linestatus
        ORDER BY l_returnflag, l_linestatus
    """,
    2: """
        SELECT s_acctbal, s_name, n_name, p_partkey, p_mfgr, s_address, s_phone, s_comment
        FROM part, supplier, partsupp, nation, region
        WHERE p_partkey = ps_partkey AND s_suppkey = ps_suppkey
          AND p_size = 15 AND p_type LIKE '%BRASS'
          AND s_nationkey = n_nationkey AND n_regionkey = r_regionkey
          AND r_name = 'EUROPE'
          AND ps_supplycost = (
              SELECT MIN(ps_supplycost) FROM partsupp, supplier, nation, region
              WHERE p_partkey = ps_partkey AND s_suppkey = ps_suppkey
                AND s_nationkey = n_nationkey AND n_regionkey = r_regionkey
                AND r_name = 'EUROPE')
        ORDER BY s_acctbal DESC, n_name, s_name, p_partkey
        LIMIT 100
    """,
    3: """
        SELECT l_orderkey, SUM(l_extendedprice * (1 - l_discount)) AS revenue,
               o_orderdate, o_shippriority
        FROM customer, orders, lineitem
        WHERE c_mktsegment = 'BUILDING' AND c_custkey = o_custkey
          AND l_orderkey = o_orderkey AND o_orderdate < DATE '1995-03-15'
          AND l_shipdate > DATE '1995-03-15'
        GROUP BY l_orderkey, o_orderdate, o_shippriority
        ORDER BY revenue DESC, o_orderdate
        LIMIT 10
    """,
    4: """
        SELECT o_orderpriority, COUNT(*) AS order_count
        FROM orders
        WHERE o_orderdate >= DATE '1993-07-01' AND o_orderdate < DATE '1993-10-01'
          AND EXISTS (SELECT 1 FROM lineitem
                      WHERE l_orderkey = o_orderkey AND l_commitdate < l_receiptdate)
        GROUP BY o_orderpriority
        ORDER BY o_orderpriority
    """,
    5: """
        SELECT n_name, SUM(l_extendedprice * (1 - l_discount)) AS revenue
        FROM customer, orders, lineitem, supplier, nation, region
        WHERE c_custkey = o_custkey AND l_orderkey = o_orderkey
          AND l_suppkey = s_suppkey AND c_nationkey = s_nationkey
          AND s_nationkey = n_nationkey AND n_regionkey = r_regionkey
          AND r_name = 'ASIA' AND o_orderdate >= DATE '1994-01-01'
          AND o_orderdate < DATE '1995-01-01'
        GROUP BY n_name
        ORDER BY revenue DESC
    """,
    6: """
        SELECT SUM(l_extendedprice * l_discount) AS revenue
        FROM lineitem
        WHERE l_shipdate >= DATE '1994-01-01' AND l_shipdate < DATE '1995-01-01'
          AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24
    """,
    7: """
        SELECT supp_nation, cust_nation, l_year, SUM(volume) AS revenue
        FROM (SELECT n1.n_name AS supp_nation, n2.n_name AS cust_nation,
                     EXTRACT(YEAR FROM l_shipdate) AS l_year,
                     l_extendedprice * (1 - l_discount) AS volume
              FROM supplier, lineitem, orders, customer, nation n1, nation n2
              WHERE s_suppkey = l_suppkey AND o_orderkey = l_orderkey
                AND c_custkey = o_custkey AND s_nationkey = n1.n_nationkey
                AND c_nationkey = n2.n_nationkey
                AND ((n1.n_name = 'FRANCE' AND n2.n_name = 'GERMANY')
                     OR (n1.n_name = 'GERMANY' AND n2.n_name = 'FRANCE'))
                AND l_shipdate BETWEEN DATE '1995-01-01' AND DATE '1996-12-31'
             ) AS shipping
        GROUP BY supp_nation, cust_nation, l_year
        ORDER BY supp_nation, cust_nation, l_year
    """,
    8: """
        SELECT o_year,
               SUM(CASE WHEN nation = 'BRAZIL' THEN volume ELSE 0 END) / SUM(volume) AS mkt_share
        FROM (SELECT EXTRACT(YEAR FROM o_orderdate) AS o_year,
                     l_extendedprice * (1 - l_discount) AS volume,
                     n2.n_name AS nation
              FROM part, supplier, lineitem, orders, customer, nation n1, nation n2, region
              WHERE p_partkey = l_partkey AND s_suppkey = l_suppkey
                AND l_orderkey = o_orderkey AND o_custkey = c_custkey
                AND c_nationkey = n1.n_nationkey AND n1.n_regionkey = r_regionkey
                AND r_name = 'AMERICA' AND s_nationkey = n2.n_nationkey
                AND o_orderdate BETWEEN DATE '1995-01-01' AND DATE '1996-12-31'
                AND p_type = 'ECONOMY ANODIZED STEEL'
             ) AS all_nations
        GROUP BY o_year
        ORDER BY o_year
    """,
    9: """
        SELECT nation, o_year, SUM(amount) AS sum_profit
        FROM (SELECT n_name AS nation, EXTRACT(YEAR FROM o_orderdate) AS o_year,
                     l_extendedprice * (1 - l_discount) - ps_supplycost * l_quantity AS amount
              FROM part, supplier, lineitem, partsupp, orders, nation
              WHERE s_suppkey = l_suppkey AND ps_suppkey = l_suppkey
                AND ps_partkey = l_partkey AND p_partkey = l_partkey
                AND o_orderkey = l_orderkey AND s_nationkey = n_nationkey
                AND p_name LIKE '%green%'
             ) AS profit
        GROUP BY nation, o_year
        ORDER BY nation, o_year DESC
    """,
    10: """
        SELECT c_custkey, c_name, SUM(l_extendedprice * (1 - l_discount)) AS revenue,
               c_acctbal, n_name, c_address, c_phone, c_comment
        FROM customer, orders, lineitem, nation
        WHERE c_custkey = o_custkey AND l_orderkey = o_orderkey
          AND o_orderdate >= DATE '1993-10-01' AND o_orderdate < DATE '1994-01-01'
          AND l_returnflag = 'R' AND c_nationkey = n_nationkey
        GROUP BY c_custkey, c_name, c_acctbal, c_phone, n_name, c_address, c_comment
        ORDER BY revenue DESC
        LIMIT 20
    """,
    11: """
        SELECT ps_partkey, SUM(ps_supplycost * ps_availqty) AS "value"
        FROM partsupp, supplier, nation
        WHERE ps_suppkey = s_suppkey AND s_nationkey = n_nationkey
          AND n_name = 'GERMANY'
        GROUP BY ps_partkey
        HAVING SUM(ps_supplycost * ps_availqty) > (
            SELECT SUM(ps_supplycost * ps_availqty) * 0.0001
            FROM partsupp, supplier, nation
            WHERE ps_suppkey = s_suppkey AND s_nationkey = n_nationkey
              AND n_name = 'GERMANY')
        ORDER BY "value" DESC
    """,
    12: """
        SELECT l_shipmode,
               SUM(CASE WHEN o_orderpriority = '1-URGENT' OR o_orderpriority = '2-HIGH'
                        THEN 1 ELSE 0 END) AS high_line_count,
               SUM(CASE WHEN o_orderpriority <> '1-URGENT' AND o_orderpriority <> '2-HIGH'
                        THEN 1 ELSE 0 END) AS low_line_count
        FROM orders, lineitem
        WHERE o_orderkey = l_orderkey AND l_shipmode IN ('MAIL', 'SHIP')
          AND l_commitdate < l_receiptdate AND l_shipdate < l_commitdate
          AND l_receiptdate >= DATE '1994-01-01' AND l_receiptdate < DATE '1995-01-01'
        GROUP BY l_shipmode
        ORDER BY l_shipmode
    """,
    13: """
        SELECT c_count, COUNT(*) AS custdist
        FROM (SELECT c_custkey, COUNT(o_orderkey) AS c_count
              FROM customer LEFT JOIN orders
                ON c_custkey = o_custkey AND o_comment NOT LIKE '%special%requests%'
              GROUP BY c_custkey) AS c_orders
        GROUP BY c_count
        ORDER BY custdist DESC, c_count DESC
    """,
    14: """
        SELECT 100.00 * SUM(CASE WHEN p_type LIKE 'PROMO%'
                                 THEN l_extendedprice * (1 - l_discount) ELSE 0 END)
               / SUM(l_extendedprice * (1 - l_discount)) AS promo_revenue
        FROM lineitem, part
        WHERE l_partkey = p_partkey AND l_shipdate >= DATE '1995-09-01'
          AND l_shipdate < DATE '1995-10-01'
    """,
    15: """
        WITH revenue AS (
            SELECT l_suppkey AS supplier_no,
                   SUM(l_extendedprice * (1 - l_discount)) AS total_revenue
            FROM lineitem
            WHERE l_shipdate >= DATE '1996-01-01' AND l_shipdate < DATE '1996-04-01'
            GROUP BY l_suppkey)
        SELECT s_suppkey, s_name, s_address, s_phone, total_revenue
        FROM supplier, revenue
        WHERE s_suppkey = supplier_no
          AND total_revenue = (SELECT MAX(total_revenue) FROM revenue)
        ORDER BY s_suppkey
    """,
    16: """
        SELECT p_brand, p_type, p_size, COUNT(DISTINCT ps_suppkey) AS supplier_cnt
        FROM partsupp, part
        WHERE p_partkey = ps_partkey AND p_brand <> 'Brand#45'
          AND p_type NOT LIKE 'MEDIUM POLISHED%'
          AND p_size IN (49, 14, 23, 45, 19, 3, 36, 9)
          AND ps_suppkey NOT IN (SELECT s_suppkey FROM supplier
                                 WHERE s_comment LIKE '%Customer%Complaints%')
        GROUP BY p_brand, p_type, p_size
        ORDER BY supplier_cnt DESC, p_brand, p_type, p_size
    """,
    17: """
        SELECT SUM(l_extendedprice) / 7.0 AS avg_yearly
        FROM lineitem, part
        WHERE p_partkey = l_partkey AND p_brand = 'Brand#23'
          AND p_container = 'MED BOX'
          AND l_quantity < (SELECT 0.2 * AVG(l_quantity) FROM lineitem
                            WHERE l_partkey = p_partkey)
    """,
    18: """
        SELECT c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice, SUM(l_quantity) AS total_qty
        FROM customer, orders, lineitem
        WHERE o_orderkey IN (SELECT l_orderkey FROM lineitem
                             GROUP BY l_orderkey HAVING SUM(l_quantity) > 250)
          AND c_custkey = o_custkey AND o_orderkey = l_orderkey
        GROUP BY c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
        ORDER BY o_totalprice DESC, o_orderdate
        LIMIT 100
    """,
    19: """
        SELECT SUM(l_extendedprice * (1 - l_discount)) AS revenue
        FROM lineitem, part
        WHERE (p_partkey = l_partkey AND p_brand = 'Brand#12'
               AND p_container IN ('SM CASE', 'SM BOX', 'SM PACK', 'SM PKG')
               AND l_quantity >= 1 AND l_quantity <= 11 AND p_size BETWEEN 1 AND 5
               AND l_shipmode IN ('AIR', 'REG AIR')
               AND l_shipinstruct = 'DELIVER IN PERSON')
           OR (p_partkey = l_partkey AND p_brand = 'Brand#23'
               AND p_container IN ('MED BAG', 'MED BOX', 'MED PKG', 'MED PACK')
               AND l_quantity >= 10 AND l_quantity <= 20 AND p_size BETWEEN 1 AND 10
               AND l_shipmode IN ('AIR', 'REG AIR')
               AND l_shipinstruct = 'DELIVER IN PERSON')
           OR (p_partkey = l_partkey AND p_brand = 'Brand#34'
               AND p_container IN ('LG CASE', 'LG BOX', 'LG PACK', 'LG PKG')
               AND l_quantity >= 20 AND l_quantity <= 30 AND p_size BETWEEN 1 AND 15
               AND l_shipmode IN ('AIR', 'REG AIR')
               AND l_shipinstruct = 'DELIVER IN PERSON')
    """,
    20: """
        SELECT s_name, s_address
        FROM supplier, nation
        WHERE s_suppkey IN (
            SELECT ps_suppkey FROM partsupp
            WHERE ps_partkey IN (SELECT p_partkey FROM part WHERE p_name LIKE 'forest%')
              AND ps_availqty > (SELECT 0.5 * SUM(l_quantity) FROM lineitem
                                 WHERE l_partkey = ps_partkey AND l_suppkey = ps_suppkey
                                   AND l_shipdate >= DATE '1994-01-01'
                                   AND l_shipdate < DATE '1995-01-01'))
          AND s_nationkey = n_nationkey AND n_name = 'CANADA'
        ORDER BY s_name
    """,
    21: """
        SELECT s_name, COUNT(*) AS numwait
        FROM supplier, lineitem l1, orders, nation
        WHERE s_suppkey = l1.l_suppkey AND o_orderkey = l1.l_orderkey
          AND o_orderstatus = 'F' AND l1.l_receiptdate > l1.l_commitdate
          AND EXISTS (SELECT 1 FROM lineitem l2
                      WHERE l2.l_orderkey = l1.l_orderkey
                        AND l2.l_suppkey <> l1.l_suppkey)
          AND NOT EXISTS (SELECT 1 FROM lineitem l3
                          WHERE l3.l_orderkey = l1.l_orderkey
                            AND l3.l_suppkey <> l1.l_suppkey
                            AND l3.l_receiptdate > l3.l_commitdate)
          AND s_nationkey = n_nationkey AND n_name = 'SAUDI ARABIA'
        GROUP BY s_name
        ORDER BY numwait DESC, s_name
        LIMIT 100
    """,
    22: """
        SELECT cntrycode, COUNT(*) AS numcust, SUM(c_acctbal) AS totacctbal
        FROM (SELECT SUBSTRING(c_phone FROM 1 FOR 2) AS cntrycode, c_acctbal
              FROM customer
              WHERE SUBSTRING(c_phone FROM 1 FOR 2) IN ('13', '31', '23', '29', '30', '18', '17')
                AND c_acctbal > (SELECT AVG(c_acctbal) FROM customer
                                 WHERE c_acctbal > 0.00
                                   AND SUBSTRING(c_phone FROM 1 FOR 2)
                                       IN ('13', '31', '23', '29', '30', '18', '17'))
                AND NOT EXISTS (SELECT 1 FROM orders WHERE o_custkey = c_custkey)
             ) AS custsale
        GROUP BY cntrycode
        ORDER BY cntrycode
    """,
}
